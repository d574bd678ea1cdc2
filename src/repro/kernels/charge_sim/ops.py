"""Jitted public wrappers for the charge_sim kernel.

`margin_sweep` is the primary entry point: a dense (cells x combos)
margin grid with a *per-combo* temperature column and per-cell, per-op
refresh-interval overrides — one dispatch covers a whole
multi-temperature, multi-operation profiling campaign (the declarative
front end lives in `repro.core.sweep.MarginEngine`).  `combo_margins`
is the single-temperature special case kept for simple callers.

Both pad the (cells, combos) grid to block multiples, transpose the
small parameter vectors into lane-aligned layout, dispatch to the
Pallas kernel on TPU (or `interpret=True` when requested) and to the
pure-jnp oracle on CPU, then unpad.  `padded_margin_sweep` stops
before the unpadding, for a caller that reduces the padded grids in a
program of its own.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.charge import ChargeConstants, DEFAULT_CONSTANTS
from repro.kernels import resolve_impl
from repro.kernels.charge_sim import charge_sim, ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value: float) -> jnp.ndarray:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


def _override_col(n: int, trefi_cells: jnp.ndarray | None) -> jnp.ndarray:
    """[n, 1] per-cell trefi override column; -1 means 'use the combo's'."""
    if trefi_cells is None:
        return jnp.full((n, 1), -1.0, jnp.float32)
    return trefi_cells.reshape(n, 1).astype(jnp.float32)


def margin_sweep(cells: jnp.ndarray, combos: jnp.ndarray,
                 temps_combo: jnp.ndarray,
                 constants: ChargeConstants = DEFAULT_CONSTANTS,
                 impl: str = "auto",
                 trefi_read_cells: jnp.ndarray | None = None,
                 trefi_write_cells: jnp.ndarray | None = None,
                 bc: int | None = None, bm: int | None = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cells: [n, 5]; combos: [m, 5]; temps_combo: [m] per-combo test
    temperature -> (read, write) margins [n, m] in ONE dispatch.

    trefi_read_cells / trefi_write_cells: optional [n] per-cell refresh
    intervals for the read / write test (folds per-module, per-op safe
    refresh intervals into one batched sweep).
    impl: 'auto' (pallas on TPU, ref elsewhere), 'pallas' (compiled
    for the TPU; raises elsewhere), 'pallas_interpret' (kernel body on
    the host — used by kernel tests), 'ref'.
    """
    n, m = cells.shape[0], combos.shape[0]
    read_m, write_m = padded_margin_sweep(
        cells, combos, temps_combo, constants, impl, trefi_read_cells,
        trefi_write_cells, bc=bc, bm=bm)
    return read_m[:n, :m], write_m[:n, :m]


def padded_margin_sweep(cells: jnp.ndarray, combos: jnp.ndarray,
                        temps_combo: jnp.ndarray,
                        constants: ChargeConstants = DEFAULT_CONSTANTS,
                        impl: str = "auto",
                        trefi_read_cells: jnp.ndarray | None = None,
                        trefi_write_cells: jnp.ndarray | None = None,
                        bc: int | None = None, bm: int | None = None
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`margin_sweep` without its unpadding: the grids as the kernel
    wrote them, padded to its blocks, with the margins in the first n
    rows and m columns (the ref impl pads nothing)."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.margin_sweep(cells, combos, temps_combo, constants,
                                trefi_read_cells, trefi_write_cells)

    bc = bc or charge_sim.BLOCK_CELLS
    bm = bm or charge_sim.BLOCK_COMBOS
    n, m = cells.shape[0], combos.shape[0]

    cells7 = jnp.concatenate(
        [cells.astype(jnp.float32),
         _override_col(n, trefi_read_cells),
         _override_col(n, trefi_write_cells)], axis=1)
    cells_t = _pad_to(cells7, 0, bc, 1.0).T
    combos6 = jnp.concatenate(
        [combos.astype(jnp.float32),
         jnp.asarray(temps_combo, jnp.float32).reshape(m, 1)], axis=1)
    # pad combos with the standard (always-safe) combo to avoid NaNs
    combos_t = _pad_to(combos6, 0, bm, 100.0).T

    return charge_sim.margin_grid(
        cells_t, combos_t, constants,
        interpret=(impl == "pallas_interpret"), bc=bc, bm=bm)


def combo_margins(cells: jnp.ndarray, combos: jnp.ndarray, temp_c: float,
                  constants: ChargeConstants = DEFAULT_CONSTANTS,
                  impl: str = "auto", trefi_cells: jnp.ndarray | None = None,
                  bc: int | None = None, bm: int | None = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cells: [n, 5]; combos: [m, 5] -> (read, write) margins [n, m] at
    one temperature (scalar-temp shim over `margin_sweep`)."""
    temps = jnp.full((combos.shape[0],), float(temp_c), jnp.float32)
    return margin_sweep(cells, combos, temps, constants, impl,
                        trefi_cells, trefi_cells, bc=bc, bm=bm)


def margin_grid_flops(n_cells: int, n_combos: int) -> int:
    """Roofline helper: approximate flops of one margin grid."""
    per_elem = 30 * charge_sim._FIXED_POINT_ITERS + 80
    return int(n_cells) * int(n_combos) * per_elem


__all__ = ["margin_sweep", "padded_margin_sweep", "combo_margins",
           "margin_grid_flops"]
