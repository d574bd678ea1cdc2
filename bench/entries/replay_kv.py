"""Entry `replay_kv`: YCSB workload A on one dual-rank DDR4 channel.

The timed call is `ALDRAMController.evaluate_system` with the traffic
passed in place of the workload pool, on a `SimEngine(backend=<config
backend>)`: the controller looks up the all-module-safe row of every
temperature bin in the frozen profiled table, and one dispatch
synthesizes the YCSB streams from the seed (Zipf keys, FNV scramble,
address map) and replays every stream under every page policy and row
(the configuration's JEDEC row first) with the rank and bank-group
constraints, returning per-lane mean / p99 latency, runtime and summed
constraint stall.

The check replays a sample of the streams, drawn from the seed, with
the plain reference (`reference.ycsb` + `reference.replay_ddr4`): the
same streams, FCFS, the rows taken from the same table, and compares
mean, p99, runtime and constraint stall of every sampled (stream,
policy, row) cell.
"""

from __future__ import annotations

import numpy as np

from compare import max_rel_of, sample
from reference import replay_ddr4 as RD
from reference import traffic as TR
from reference import ycsb as Y
from work import request_replays

KEYS = ("mean", "p99", "total", "stall")


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
        from repro.core.dram_sim import KVSpec, Policy
        from repro.core.sim_engine import SimEngine
        from repro.core.timing import RankTimings, TimingParams
        from repro.core.variation import Population

        self.config, self.traffic, self.seed = config, traffic, seed
        geo, y = config["geometry"], traffic["ycsb"]
        self.spec = KVSpec(n=int(traffic["n_requests"]),
                           n_streams=int(traffic["n_streams"]), seed=seed,
                           update_frac=float(y["update_frac"]),
                           theta=float(y["zipf_theta"]),
                           n_records=int(y["n_records"]),
                           op_interval_ns=float(y["op_interval_ns"]))
        if ((geo["channels"], geo["ranks"], geo["bank_groups"],
             geo["banks"]) != (1, KVSpec.n_ranks, KVSpec.bank_groups,
                               KVSpec.n_banks)
                or (y["record_bytes"], y["fields"], y["field_bytes"])
                != (64 * KVSpec.LINES, KVSpec.FIELDS,
                    KVSpec.FIELD_BYTES)):
            raise ValueError("the configuration or traffic differs from "
                             "KVSpec's channel and record layout")
        self.table = TR.load_json(config["table"])
        self.std_row = config["timing_standard"]["row"]
        self.rank_ns = config["rank_timings"]["ns"]
        bins = tuple(float(b) for b in self.table["temp_bins"])
        self.policies = traffic["policies"]
        self.ctrl = ALDRAMController(temp_bins=bins, per_bank=False)
        self.ctrl.table = TimingTable(
            bins, np.asarray(self.table["params_module"], np.float32),
            np.asarray(self.table["safe_trefi_read"], np.float32),
            np.asarray(self.table["safe_trefi_write"], np.float32))
        # the replay reads only the bank count of the population
        self.pop = Population(np.zeros((1, 1, geo["banks"], 1, 5),
                                       np.float32))
        self.engine = SimEngine(backend=config["backend"])
        self.kw = dict(std=TimingParams.from_row(self.std_row),
                       rank_timings=RankTimings(*self.rank_ns),
                       t_burst_ns=float(config["replay"]["t_burst_ns"]),
                       policies=tuple(Policy(**p) for p in self.policies))
        self.lanes = len(self.policies) * (1 + len(bins))
        self.work = {"request_replays": request_replays(
            self.spec.n_streams, self.spec.n, self.lanes)}

    def call(self) -> dict:
        out = self.ctrl.evaluate_system(self.pop, traffic=self.spec,
                                        engine=self.engine, **self.kw)
        r = out["result"]
        return {"mean": r.mean_latency_ns, "p99": r.p99_latency_ns,
                "total": r.total_ns, "stall": r.constraint_stall_ns}

    def release(self) -> None:
        self.ctrl = self.engine = self.spec = None

    # ------------------------------------------------------------ check
    def sampled(self) -> np.ndarray:
        return sample(self.seed, int(self.traffic["n_streams"]),
                      int(self.traffic["check"]["sample_streams"]), 3)

    def reference(self, dtype=np.float32) -> dict:
        """Reference mean / p99 / total / stall of the sampled streams,
        shaped [sampled, policies, rows]."""
        cfg, n = self.config, int(self.traffic["n_requests"])
        streams = [Y.stream(self.seed, int(i), n, self.traffic["ycsb"])
                   for i in self.sampled()]
        return RD.campaign(streams, self.policies,
                           table_rows(self.table, self.std_row),
                           self.rank_ns, n_ranks=cfg["geometry"]["ranks"],
                           n_banks=cfg["geometry"]["banks"],
                           n_groups=cfg["geometry"]["bank_groups"],
                           mlp_window=cfg["replay"]["mlp_window"],
                           t_burst=cfg["replay"]["t_burst_ns"], dtype=dtype)

    @staticmethod
    def number(got: dict, ref: dict) -> float:
        """stats_max_rel: the widest relative gap of mean, p99, runtime
        and constraint stall over the sampled cells."""
        return max_rel_of(got, ref, KEYS)

    def select(self, out: dict) -> dict:
        """The compared part of a call's output."""
        idx = self.sampled()
        return {k: np.asarray(v)[idx] for k, v in out.items()}
