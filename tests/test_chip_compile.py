"""Compile rehearsal for the TPU: the margin kernel and both replay
kernels, compiled for a described (not attached) v5e chip at the sizes
`chip_smoke.py` runs.  Interpret mode cannot see what these catch:
block shapes off the (8, 128) tiling, scalar reads the chip cannot
serve, and more SMEM or VMEM than a program may use.  Nothing runs, so
these say nothing about results or times.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the one given this file
loads the TPU compiler."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import aldram, faults, thermal
from repro.core.calibration import CALIBRATED_VARIATION
from repro.core.charge import DEFAULT_CONSTANTS
from repro.core.timing import (DDR3_1600, DDR4_2400_RANK,
                               DDR4_2400_TCK_NS, read_combo_grid,
                               refresh_grid, write_combo_grid)
from repro.kernels.charge_sim import charge_sim
from repro.kernels.replay import replay

N = 8192                       # requests per trace (Fig. 4 scale)
G_FIG4 = 70                    # 35 workloads x 2 core modes, 1 policy
G_TRAFFIC = 16 * 3             # 16 tenant mixes x 3 interleaves
V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    return fn.lower(*args, **kw).compile()


# ------------------------------------------------------------ margins
def _profile_columns() -> int:
    """Combo columns of the non-fast profile's timing campaign."""
    n = (read_combo_grid(DDR3_1600, 1.25).shape[0]
         + write_combo_grid(DDR3_1600, 1.25).shape[0])
    return len(aldram.DEFAULT_TEMP_BINS) * n


def _pad(x: int, m: int) -> int:
    return -(-x // m) * m


def _cells_per_module() -> int:
    v = CALIBRATED_VARIATION
    return v.n_chips * v.n_banks * v.n_cells


@pytest.mark.parametrize("campaign", ["refresh", "profile-group",
                                      "verify-group"])
def test_margin_grid_compiles(one_chip, campaign):
    """The margin kernel at each dispatch shape of the 115-module
    profile -> verify path."""
    cpm, m = _cells_per_module(), CALIBRATED_VARIATION.n_modules
    cols = _profile_columns()
    if campaign == "refresh":
        cells, combos = m * cpm, refresh_grid().shape[0]
    elif campaign == "profile-group":
        g = aldram.PROFILE_GRID_ELEMS // (cpm * cols)
        assert 1 <= g < m
        cells, combos = g * cpm, cols
    else:
        # verify's module groups: 5 bins x (1 envelope + 8 bank) rows
        vcols = len(aldram.DEFAULT_TEMP_BINS) * 9
        g = int((8_000_000 / (cpm * vcols)) ** 0.5)
        cells, combos = g * cpm, g * vcols
    bc, bm = charge_sim.BLOCK_CELLS, charge_sim.BLOCK_COMBOS
    c = _compile(charge_sim.margin_grid,
                 _sds(one_chip, (7, _pad(cells, bc))),
                 _sds(one_chip, (6, _pad(combos, bm))),
                 DEFAULT_CONSTANTS)
    assert c.memory_analysis().output_size_in_bytes < V5E_HBM // 4


@pytest.mark.parametrize("campaign", ["refresh", "profile-group"])
def test_pass_envelopes_compile(one_chip, campaign):
    """The device reduction of the profile's margin grids to pass
    envelopes, at the kernel's padded output shapes: it reads the
    float32 grids without a copy of them (its scratch is under a
    quarter of their bytes) and returns only booleans."""
    from repro.core.sweep import _pass_envelopes
    cpm, m = _cells_per_module(), CALIBRATED_VARIATION.n_modules
    v = CALIBRATED_VARIATION
    if campaign == "refresh":
        cols = refresh_grid().shape[0]
        cell_shape = (m, v.n_chips, v.n_banks, v.n_cells)
        axes, blocks = (3,), ((0, 0, cols), (1, 0, cols))
    else:
        cols = _profile_columns()
        g = aldram.PROFILE_GRID_ELEMS // (cpm * cols)
        cell_shape = (g, v.n_chips, v.n_banks, 1, v.n_cells)
        half = len(aldram.DEFAULT_TEMP_BINS) * read_combo_grid(
            DDR3_1600, 1.25).shape[0]
        axes, blocks = (1, 4), ((0, 0, half), (1, half, cols))
    n = cell_shape[0] * cpm
    grid = _sds(one_chip, (_pad(n, charge_sim.BLOCK_CELLS),
                           _pad(cols, charge_sim.BLOCK_COMBOS)))
    c = _compile(_pass_envelopes, grid, grid, cell_shape=cell_shape,
                 axes=axes, blocks=blocks)
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes // 4
    assert mem.output_size_in_bytes < n * cols // 8


def test_full_profile_campaign_is_chunked(one_chip):
    """The whole 115-module timing campaign as ONE dispatch: its two
    margin grids alone take most of a v5e's HBM until they are reduced
    to envelopes — which is why `profile` runs it in module groups."""
    cpm, m = _cells_per_module(), CALIBRATED_VARIATION.n_modules
    cols = _profile_columns()
    c = _compile(charge_sim.margin_grid,
                 _sds(one_chip, (7, _pad(m * cpm, 256))),
                 _sds(one_chip, (6, _pad(cols, 256))),
                 DEFAULT_CONSTANTS)
    mem = c.memory_analysis()
    print(f"full-size profile campaign ({m * cpm} cells x {cols} "
          f"columns): {mem}")
    out = mem.output_size_in_bytes
    assert 2 * out > V5E_HBM            # over half the chip's HBM
    assert m * cpm * cols > aldram.PROFILE_GRID_ELEMS


# ------------------------------------------------------------- replay
def _streams(sh, g, n):
    return [_sds(sh, (g, n))] + [_sds(sh, (g, n), jnp.int32)] * 4


@pytest.mark.parametrize("variant", ["static", "per-bank", "4-channel",
                                     "faulted", "regions", "ddr4-groups"])
def test_replay_blocks_compiles(one_chip, variant):
    """The static replay kernel at the smoke run's shapes: the Fig. 4
    campaign (per-module and per-bank rows; the fused thermal
    campaign's static bracket has the same shape), the traffic
    campaign's 4 channels, a faulted and a region-compressed launch,
    and the YCSB cell's dual-rank DDR4 channel with its rank and
    bank-group state (128 streams x 2 policies x 16384 requests)."""
    sh = one_chip
    g = G_TRAFFIC if variant == "4-channel" else G_FIG4
    n = N
    tim = _sds(sh, (6, 128))
    kw = {}
    if variant == "ddr4-groups":
        g, n = 256, 16384
        kw["n_banks"] = 16
        kw["chan"] = (1, 2, 4 * DDR4_2400_TCK_NS,
                      (4,) + DDR4_2400_RANK.as_tuple())
    elif variant == "per-bank":
        tim = _sds(sh, (8, 6, 128))
    elif variant == "4-channel":
        kw["chan"] = (4, 1, 5.0)
    elif variant == "faulted":
        kw["fault"] = (_sds(sh, (faults.F_COLS, 128)), _sds(sh, (6, 1)),
                       _sds(sh, (g, N)))
    elif variant == "regions":
        tim = _sds(sh, (13, 6, 128))
        kw["region_map"] = _sds(sh, (64, 128), jnp.int32)
    c = _compile(replay.replay_blocks, _sds(sh, (g, 1)),
                 _sds(sh, (g, 1), jnp.int32), *_streams(sh, g, n), tim,
                 **kw)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("variant", ["fused-thermal", "per-bank",
                                     "emit-raw", "faulted"])
def test_adaptive_blocks_compiles(one_chip, variant):
    """The adaptive kernel at the fused thermal campaign's shape (one
    table stack x 4 scenarios + their oracles = 8 lanes), per-bank,
    with the raw temperature/bin traces, and faulted."""
    sh = one_chip
    lanes, n_bins = 8, len(aldram.DEFAULT_TEMP_BINS)
    tab = _sds(sh, (n_bins + 1, 6, lanes))
    kw = {"bs": lanes}
    if variant == "per-bank":
        tab = _sds(sh, (8, n_bins + 1, 6, lanes))
    elif variant == "emit-raw":
        kw["emit_raw"] = True
    elif variant == "faulted":
        kw["fault"] = (_sds(sh, (faults.F_COLS, lanes)),
                       _sds(sh, (G_FIG4, N)))
    c = _compile(replay.adaptive_blocks, _sds(sh, (G_FIG4, 1)),
                 *_streams(sh, G_FIG4, N), tab,
                 _sds(sh, (thermal.SCN_COLS, lanes)),
                 _sds(sh, (n_bins, lanes)), _sds(sh, (6, 1)), **kw)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel", ["static", "static-faulted",
                                    "adaptive-emit-raw"])
def test_largest_request_count_compiles(one_chip, kernel):
    """Each kernel compiles at the largest N `replay.max_requests`
    states for it (SMEM holds the request fields, VMEM the raw
    tiles); one more request raises a clear error before lowering."""
    sh, g = one_chip, 8
    faulted = kernel == "static-faulted"
    raw = kernel == "adaptive-emit-raw"
    # static: 5 request fields + the latency tile; adaptive: + the heat
    # decay field, the ambient tile and the two raw-trace tiles
    n = (replay.max_requests(6, 4) if raw
         else replay.max_requests(5 + faulted, 1))

    def launch(n):
        if raw:
            return _compile(replay.adaptive_blocks, _sds(sh, (g, 1)),
                            *_streams(sh, g, n), _sds(sh, (2, 6, 128)),
                            _sds(sh, (thermal.SCN_COLS, 128)),
                            _sds(sh, (1, 128)), _sds(sh, (6, 1)),
                            emit_raw=True)
        fault = ((_sds(sh, (faults.F_COLS, 128)), _sds(sh, (6, 1)),
                  _sds(sh, (g, n))) if faulted else None)
        return _compile(replay.replay_blocks, _sds(sh, (g, 1)),
                        _sds(sh, (g, 1), jnp.int32), *_streams(sh, g, n),
                        _sds(sh, (6, 128)), fault=fault)

    assert "tpu_custom_call" in launch(n).as_text()
    with pytest.raises(ValueError, match="requests per stream"):
        launch(n + 8)
    assert n >= N
