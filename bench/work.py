"""Work of one entry call, counted from the campaign's shapes.

The counts depend only on what a campaign asks for, never on how the
program computes it, so a faster implementation is credited for the
same work."""

from __future__ import annotations


def request_replays(streams: int, requests: int, lanes: int) -> int:
    """Simulated request-replays of a campaign: every valid request of
    every stream, replayed once per lane (timing row x policy, or
    thermal scenario x table x policy, static bracket included)."""
    return int(streams) * int(requests) * int(lanes)


def bracket_lanes(policies: int, scenarios: int) -> int:
    """Lanes per stream of the thermal bracket: the adaptive replay of
    each scenario and of its oracle variant, plus the static replay of
    the JEDEC baseline and of each scenario's worst-case row."""
    return int(policies) * (2 * int(scenarios) + 1 + int(scenarios))


def margin_evals(cells: int, refresh_points: int, temps: int,
                 combos: list[int]) -> int:
    """Margin evaluations of one profile: the read and the write margin
    grid (two elements per cell and column) of the refresh campaign
    (one column per refresh interval) and of the timing campaign (one
    column per temperature bin and combo of each test)."""
    columns = int(refresh_points) + int(temps) * sum(int(c) for c in combos)
    return 2 * int(cells) * columns
