"""Entry `replay_static`: the Fig. 4 campaign of one DDR3 channel.

The timed call is `ALDRAMController.evaluate_system` on a
`SimEngine(backend=<config backend>)`: the controller looks up the
all-module-safe row of every temperature bin in the frozen profiled
table, synthesizes the 70-stream pool from the seed, replays every
stream under every policy and row (JEDEC baseline first), and returns
per-cell mean / p99 latency and runtime with the CPI speedup summaries.

The check replays a sample of the streams, drawn from the seed, with
the plain reference (`reference.traffic` + `reference.replay`): the
same streams, the FR-FCFS-lite order of each policy, the rows taken
from the same table, and compares mean, p99 and runtime of every
sampled (stream, policy, row) cell.
"""

from __future__ import annotations

import numpy as np

from compare import max_rel_of, sample
from reference import replay as R
from reference import traffic as TR
from work import request_replays

def table_rows(table: dict, std_row) -> np.ndarray:
    """[1 + bins, 6] rows: the JEDEC row, then per temperature bin the
    all-module-safe row (largest of each parameter over the modules)."""
    p = np.asarray(table["params_module"], np.float32)     # [m, bins, 4]
    rows = np.repeat(np.asarray(std_row, np.float32)[None],
                     1 + p.shape[1], axis=0)
    rows[1:, :4] = p.max(axis=0)
    return rows


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        from repro.core.aldram import ALDRAMController, TimingTable
        from repro.core.dram_sim import Policy
        from repro.core.sim_engine import SimEngine
        from repro.core.variation import Population

        self.config, self.traffic, self.seed = config, traffic, seed
        self.table = TR.load_json(config["table"])
        self.std_row = config["timing_standard"]["row"]
        bins = tuple(float(b) for b in self.table["temp_bins"])
        self.n = int(traffic["n_requests"])
        self.banks = int(config["geometry"]["banks"])
        self.policies = traffic["policies"]
        self.ctrl = ALDRAMController(temp_bins=bins, per_bank=False)
        self.ctrl.table = TimingTable(
            bins, np.asarray(self.table["params_module"], np.float32),
            np.asarray(self.table["safe_trefi_read"], np.float32),
            np.asarray(self.table["safe_trefi_write"], np.float32))
        # the replay reads only the bank count of the population
        self.pop = Population(np.zeros((1, 1, self.banks, 1, 5), np.float32))
        self.engine = SimEngine(backend=config["backend"])
        self.program_policies = tuple(Policy(**p) for p in self.policies)
        pool = TR.pool(traffic["pool"])
        self.streams = len(pool["offsets"])
        self.lanes = len(self.policies) * (1 + len(bins))
        self.work = {"request_replays": request_replays(
            self.streams, self.n, self.lanes)}

    def call(self) -> dict:
        out = self.ctrl.evaluate_system(
            self.pop, n=self.n, seed=self.seed,
            policies=self.program_policies, engine=self.engine)
        r = out["result"]
        return {"mean": r.mean_latency_ns, "p99": r.p99_latency_ns,
                "total": r.total_ns}

    def release(self) -> None:
        self.ctrl = self.engine = None

    # ------------------------------------------------------------ check
    def sampled(self) -> np.ndarray:
        return sample(self.seed, self.streams,
                      int(self.traffic["check"]["sample_streams"]), 1)

    def reference(self, dtype=np.float32) -> dict:
        """Reference mean / p99 / total of the sampled streams, shaped
        [sampled, policies, rows]."""
        pool = TR.pool(self.traffic["pool"])
        cfg = self.config
        streams = [TR.pool_stream(
            self.seed, pool["offsets"][i], self.n, pool["row_hits"][i],
            pool["write_fracs"][i], pool["inter_arrivals_ns"][i],
            self.banks, cfg["geometry"]["rows"]) for i in self.sampled()]
        return R.campaign(streams, self.policies,
                          table_rows(self.table, self.std_row), self.banks,
                          cfg["replay"]["mlp_window"], dtype=dtype)

    @staticmethod
    def number(got: dict, ref: dict) -> float:
        """stats_max_rel: the widest relative gap of mean, p99 and
        runtime over the sampled cells."""
        return max_rel_of(got, ref, ("mean", "p99", "total"))

    def select(self, out: dict) -> dict:
        """The compared part of a call's output."""
        idx = self.sampled()
        return {k: np.asarray(v)[idx] for k, v in out.items()}
