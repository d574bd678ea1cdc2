"""Entry `profile`: the AL-DRAM profile of a module population.

Set-up draws a fresh calibrated population from the seed (the
configuration's hierarchy and variation, on the device).  The timed
call is `ALDRAMController.profile` with the configuration's profiler
(the margin kernel the engine resolves on the TPU): the refresh
campaign at 85 C, the read and write timing campaigns at every
temperature bin, the envelope reductions and the combo selection,
returning the module-level and per-bank register tables and the safe
refresh intervals.

The check profiles a sample of the modules, drawn from the seed, with
the plain reference (`reference.margins`) and counts the entries of
the table (module rows, per-bank rows, safe refresh intervals) that
differ.
"""

from __future__ import annotations

import numpy as np

from compare import sample
from reference import margins as M
from work import margin_evals


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        from repro.core.charge import ChargeConstants
        from repro.core.profiler import Profiler
        from repro.core.timing import TimingParams
        from repro.core.variation import Population

        self.config, self.traffic, self.seed = config, traffic, seed
        pc = config["population"]
        self.cells = M.population(seed, pc)
        self.pop = Population(self.cells)
        self.std_row = config["timing_standard"]["row"]
        self.bins = tuple(float(b) for b in config["temp_bins_c"])
        self.prof = Profiler(
            constants=ChargeConstants(**pc["charge_constants"]),
            std=TimingParams.from_row(self.std_row),
            refresh_guardband_ms=float(config["refresh_guardband_ms"]),
            impl=traffic["impl"], grid_step=float(config["grid_step_ns"]))
        step = float(config["grid_step_ns"])
        n_cells = int(np.prod(self.cells.shape[:4]))
        self.work = {"margin_evals": margin_evals(
            n_cells, len(M.refresh_grid()), len(self.bins),
            [len(M.combo_grid(op, self.std_row, step))
             for op in ("read", "write")])}

    def call(self) -> dict:
        from repro.core.aldram import ALDRAMController
        ctrl = ALDRAMController(self.prof, temp_bins=self.bins,
                                per_bank=True)
        t = ctrl.profile(self.pop)
        return {"params_module": t.params_module, "params_bank": t.params,
                "safe_trefi_read": t.safe_trefi_read,
                "safe_trefi_write": t.safe_trefi_write}

    def release(self) -> None:
        self.pop = self.prof = None

    # ------------------------------------------------------------ check
    def sampled(self) -> np.ndarray:
        return sample(self.seed, int(self.cells.shape[0]),
                      int(self.traffic["check"]["sample_modules"]), 2)

    def reference(self, dtype=np.float32) -> dict:
        """Reference table entries of the sampled modules."""
        cfg = self.config
        outs = [M.profile_module(
            self.cells[m], self.std_row, self.bins,
            cfg["population"]["charge_constants"],
            float(cfg["grid_step_ns"]), float(cfg["refresh_guardband_ms"]),
            float(cfg["refresh_test_c"]), dtype)
            for m in self.sampled()]
        return {k: np.stack([o[k] for o in outs]) for k in outs[0]}

    @staticmethod
    def number(got: dict, ref: dict) -> float:
        """table_entries_differing: register-row entries and safe
        refresh intervals of the sampled modules that differ."""
        return float(sum(int((np.asarray(got[k]) != ref[k]).sum())
                         for k in ref))

    def select(self, out: dict) -> dict:
        """The compared part of a call's output."""
        idx = self.sampled()
        return {k: np.asarray(v)[idx] for k, v in out.items()}
