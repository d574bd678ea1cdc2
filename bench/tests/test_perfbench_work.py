"""Work counts of the chip benchmark, from tiny campaign specs."""

from perfbench_util import load

W = load("work.py")


def test_request_replays():
    # 3 streams x 100 requests x (2 policies x 4 rows)
    assert W.request_replays(3, 100, 2 * 4) == 2400


def test_bracket_lanes():
    # adaptive + oracle per scenario, then JEDEC + one worst-case row
    # per scenario in the static bracket
    assert W.bracket_lanes(1, 4) == 4 + 4 + 1 + 4
    assert W.bracket_lanes(2, 1) == 2 * 4


def test_margin_evals():
    # two grids (read, write) x 10 cells x (64 refresh points +
    # 5 temperatures x (30 read + 20 write combos))
    assert W.margin_evals(10, 64, 5, [30, 20]) == 2 * 10 * (64 + 250)


def test_cell_work_follows_the_campaign_shape():
    from perfbench_util import small_cell, run_module
    run = run_module()
    _, config, traffic = small_cell("fig4-static-1ch")
    cell = run.entry_module(traffic["entry"]).Cell(config, traffic, 1, 1)
    # 70 pool streams x 128 requests x 2 policies x (JEDEC + 5 bins)
    assert cell.work == {"request_replays": 70 * 128 * 2 * 6}
