"""Entry `thermal_bracket`: the adaptive controller under dynamic
temperature, bracketed by its static deployments, on one DDR3 channel.

The timed call is `ALDRAMController.evaluate_dynamic(fused=True)` on a
`SimEngine(backend=<config backend>)`: the per-bin table stack of the
frozen profiled table (all-module-safe rows, bin-monotone, JEDEC row
last) rides one dispatch that synthesizes the 70-stream pool from the
seed, replays every stream with in-scan bin selection under each
thermal scenario and its zero-hysteresis oracle, rounds each scenario's
peak sensed temperature up to its worst-case bin, and replays every
stream under the JEDEC row and those worst-case rows.

The check replays every stream under the scenarios with the plain
reference (`reference.thermal`) to find the same peaks, and a sample
of the streams, drawn from the seed, under the oracle variants and the
static bracket; it compares the sampled streams' mean, p99, runtime,
peak and mean sensed temperature, bin switches and bank heat, the
bracket's mean latencies, and the worst-case bins.
"""

from __future__ import annotations

import numpy as np

from compare import max_rel_of, sample
from reference import replay as R
from reference import thermal as TH
from reference import traffic as TR
from work import bracket_lanes, request_replays

SCN_FIELDS = ("base_c", "amp_sin", "period_sin_ns", "amp_step",
              "t_step_ns", "amp_burst", "period_burst_ns", "duty")
KEYS = ("mean", "p99", "total", "temp_max", "temp_mean", "bin_switches",
        "bank_heat", "static_mean", "worst_bin")


def safe_stack(table: dict, std_row) -> tuple[np.ndarray, np.ndarray]:
    """([bins + 1, 6] rows, [bins] edges): per bin the all-module-safe
    row, made bin-monotone by a running max, the JEDEC row last."""
    p = np.asarray(table["params_module"], np.float32)     # [m, bins, 4]
    nb = p.shape[1]
    rows = np.repeat(np.asarray(std_row, np.float32)[None], nb + 1, axis=0)
    rows[:nb, :4] = np.maximum.accumulate(p.max(axis=0), axis=0)
    return rows, np.asarray(table["temp_bins"], np.float32)


def scenario_row(s: dict, hyst_scale: float) -> np.ndarray:
    return np.asarray([float(s.get(k, 1.0 if k.startswith("period")
                                   else 0.0)) for k in SCN_FIELDS]
                      + [hyst_scale], np.float32)


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        from repro.core.aldram import ALDRAMController, TimingTable
        from repro.core.dram_sim import Policy
        from repro.core.power import PowerParams
        from repro.core.sim_engine import SimEngine
        from repro.core.thermal import ThermalConfig, ThermalScenario
        from repro.core.variation import Population

        self.config, self.traffic, self.seed = config, traffic, seed
        self.table = TR.load_json(config["table"])
        self.std_row = config["timing_standard"]["row"]
        bins = tuple(float(b) for b in self.table["temp_bins"])
        self.n = int(traffic["n_requests"])
        self.banks = int(config["geometry"]["banks"])
        self.scenarios = traffic["scenarios"]
        tc = traffic["thermal"]
        self.tcfg = (tc["tau_ns"], tc["c_heat"], tc["hyst_c"],
                     tc["e_burst"], tc["e_act_pre"], tc["p_act_standby"])
        self.ctrl = ALDRAMController(temp_bins=bins, per_bank=False)
        self.ctrl.table = TimingTable(
            bins, np.asarray(self.table["params_module"], np.float32),
            np.asarray(self.table["safe_trefi_read"], np.float32),
            np.asarray(self.table["safe_trefi_write"], np.float32))
        # the replay reads only the bank count of the population
        self.pop = Population(np.zeros((1, 1, self.banks, 1, 5), np.float32))
        self.program_scenarios = tuple(
            ThermalScenario(name=s["name"], **{k: float(s[k]) for k in s
                                               if k != "name"})
            for s in self.scenarios)
        self.thermal = ThermalConfig(
            tau_ns=tc["tau_ns"], c_heat=tc["c_heat"], hyst_c=tc["hyst_c"],
            power=PowerParams(e_burst=tc["e_burst"],
                              e_act_pre=tc["e_act_pre"],
                              p_act_standby=tc["p_act_standby"]))
        self.policies = tuple(Policy(**p) for p in traffic["policies"])
        assert all(p.reorder_window <= 1 and not p.closed
                   for p in self.policies), "the bracket replays FCFS"
        self.engine = SimEngine(backend=config["backend"])
        pool = TR.pool(traffic["pool"])
        self.streams = len(pool["offsets"])
        self.work = {"request_replays": request_replays(
            self.streams, self.n,
            bracket_lanes(len(self.policies), len(self.scenarios)))}

    def call(self) -> dict:
        out = self.ctrl.evaluate_dynamic(
            self.pop, scenarios=self.program_scenarios, config=self.thermal,
            n=self.n, seed=self.seed, policies=self.policies,
            engine=self.engine, fused=True)
        r = out["result"]
        nc = len(self.scenarios)
        lat = out["mean_latency_ns"]                    # [2, W, P, 1+3C]
        static = lat[..., :1 + nc].reshape((-1,) + lat.shape[2:3]
                                           + (1 + nc,))
        return {"mean": r.mean_latency_ns[:, :, 0],
                "p99": r.p99_latency_ns[:, :, 0],
                "total": r.total_ns[:, :, 0],
                "temp_max": r.temp_max[:, :, 0],
                "temp_mean": r.temp_mean[:, :, 0],
                "bin_switches": r.bin_switches[:, :, 0],
                "bank_heat": r.bank_heat[:, :, 0],
                "static_mean": static,
                "worst_bin": np.asarray(out["worst_bin"])}

    def release(self) -> None:
        self.ctrl = self.engine = None

    # ------------------------------------------------------------ check
    def sampled(self) -> np.ndarray:
        return sample(self.seed, self.streams,
                      int(self.traffic["check"]["sample_streams"]), 5)

    def reference(self, dtype=np.float32) -> dict:
        """Reference statistics of the sampled streams ([sampled, P, 2C]
        adaptive, [sampled, P, 1+C] bracket) and the worst-case bins
        ([C]), with the peaks taken over every stream."""
        pool = TR.pool(self.traffic["pool"])
        rows, edges = safe_stack(self.table, self.std_row)
        cfg = self.config
        nc = len(self.scenarios)
        streams = [TR.pool_stream(self.seed, pool["offsets"][i], self.n,
                                  pool["row_hits"][i],
                                  pool["write_fracs"][i],
                                  pool["inter_arrivals_ns"][i], self.banks,
                                  cfg["geometry"]["rows"])
                   for i in range(self.streams)]
        idx = self.sampled()
        # lanes: every stream under each scenario, then the sampled
        # streams under each oracle variant (FCFS: one issue order)
        lane_stream, lane_scn = [], []
        for s in self.scenarios:
            for i in range(self.streams):
                lane_stream.append(i)
                lane_scn.append(scenario_row(s, 1.0))
        for s in self.scenarios:
            for i in idx:
                lane_stream.append(i)
                lane_scn.append(scenario_row(s, 0.0))
        fields = [np.stack([streams[i][k] for i in lane_stream])
                  for k in range(4)]
        a = TH.adaptive(*fields, rows, edges, np.stack(lane_scn),
                        self.tcfg, self.banks, cfg["replay"]["mlp_window"],
                        dtype)
        mean, p99 = R.stats(a["lat"])
        temps = a["temps"].astype(np.float64)
        per = {"mean": mean, "p99": p99,
               "total": a["total"].astype(np.float64),
               "temp_max": temps.max(-1), "temp_mean": temps.mean(-1),
               "bin_switches": (np.diff(a["bins"], axis=-1) != 0).sum(-1),
               "bank_heat": a["heat"].astype(np.float64)}
        w = self.streams
        peak = per["temp_max"][:nc * w].reshape(nc, w).max(axis=1)
        worst = np.searchsorted(edges, (peak + self.tcfg[2]).astype(
            np.float32), side="left")
        pos = {i: k for k, i in enumerate(range(self.streams))}
        sel = [np.r_[[c * w + pos[i] for c in range(nc)],
                     [nc * w + c * len(idx) + k for c in range(nc)]]
               for k, i in enumerate(idx)]
        out = {k: np.stack([v[s] for s in sel])[:, None]
               for k, v in per.items()}                  # [S, P=1, 2C]
        # the static bracket: JEDEC, then each scenario's worst row
        brow = np.concatenate([np.asarray(self.std_row, np.float32)[None],
                               rows[worst]])
        out["static_mean"] = R.campaign(
            [streams[i] for i in idx], self.traffic["policies"], brow,
            self.banks, cfg["replay"]["mlp_window"], dtype=dtype)["mean"]
        out["worst_bin"] = worst
        return out

    @staticmethod
    def number(got: dict, ref: dict) -> float:
        """stats_max_rel: the widest relative gap over every compared
        statistic of the sampled streams and the worst-case bins."""
        return max_rel_of(got, ref, KEYS)

    def select(self, out: dict) -> dict:
        """The compared part of a call's output: the sampled streams,
        and the worst-case bins."""
        idx = self.sampled()
        return {k: (np.asarray(v) if k == "worst_bin"
                    else np.asarray(v)[idx]) for k, v in out.items()}
