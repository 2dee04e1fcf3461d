"""Each device event of the traced solve, tied to the stage of the ECG
iteration that issued it.

The program traces every stage of an iteration under a ``jax.named_scope``
(``repro.observe.scopes``), so each instruction of the compiled solve
program names its stage as one ``/``-separated component of its
``op_name``: ``ecg.spmbv`` (the gather of V and the Block-ELL kernel),
``ecg.exchange`` (the halo exchange, inside ``ecg.spmbv``), ``ecg.gram``,
``ecg.factor``, ``ecg.update`` and ``ecg.check``.  An event of the solve
program whose instruction is under none of them is ``unscoped``: the glue
between stages and the loops the compiler makes of its own.

A program that names no stage (one from before the scopes) gives no
stage metric at all: every reader returns None.
"""

from __future__ import annotations

import bisect

from chipbench import trace

SPMBV = "ecg.spmbv"
EXCHANGE = "ecg.exchange"
GRAM = "ecg.gram"
FACTOR = "ecg.factor"
UPDATE = "ecg.update"
CHECK = "ecg.check"
UNSCOPED = "unscoped"

STAGES = (SPMBV, EXCHANGE, GRAM, FACTOR, UPDATE, CHECK)


def stage(instr: trace.Instr | None) -> str:
    """The innermost stage component of ``instr``'s ``op_name`` (so the
    exchange, inside the SpMBV, is ``ecg.exchange``), or ``unscoped``."""
    if instr is None:
        return UNSCOPED
    named = [c for c in instr.op_name.split("/") if c in STAGES]
    return named[-1] if named else UNSCOPED


def names_stages(hlo: trace.HloIndex) -> bool:
    """Whether the compiled program names any stage."""
    return any(stage(i) != UNSCOPED for m in hlo.by_module.values() for i in m.values())


def in_program(r, o: trace.Op) -> bool:
    """Whether event ``o`` ran the solve program: an event tied to a module
    if the module is the program's, one tied to none if the program has an
    instruction of its name."""
    return o.module in r.hlo.by_module if o.module else r.hlo.find(o) is not None


def solve_ops(r, ops: list[trace.Op]) -> list[trace.Op]:
    """The solve program's events among one chip's ``ops``, clipped to the
    traced solve."""
    return [o for o in trace.clip(ops, *r.window_ns) if in_program(r, o)]


def stage_ns(r) -> dict[str, dict[str, float]]:
    """For each chip, the device time (ns) of the solve program's events in
    the traced solve, by stage; the values add up to the program's whole
    device time on that chip."""
    out = {}
    for dev, ops in r.trace.devices.items():
        by = dict.fromkeys(STAGES + (UNSCOPED,), 0.0)
        for o in solve_ops(r, ops):
            by[stage(r.hlo.find(o))] += o.dur
        out[dev] = by
    return out


def _readable(r) -> bool:
    return r.trace is not None and bool(r.traced_iters) and names_stages(r.hlo)


def ms_per_iter(r, *stages: str) -> float | None:
    """Device time per iteration of the traced solve of the events under
    ``stages``, in ms, on the chip where it is largest; None where the
    program names no stages or no such event ran."""
    if not _readable(r):
        return None
    worst = max((sum(by[s] for s in stages) for by in stage_ns(r).values()), default=0.0)
    return worst * 1e-6 / r.traced_iters if worst > 0 else None


def uncovered_ns(mine: list[trace.Op], others: list[trace.Op]) -> float:
    """Measure of the union of ``mine``'s intervals that no event of
    ``others`` covers."""
    lo = min((o.start for o in mine + others), default=0.0)
    hi = max((o.end for o in mine + others), default=0.0)
    cover = trace.busy_intervals(others, lo, hi)
    total, j = 0.0, 0
    for s, e in trace.busy_intervals(mine, lo, hi):
        total += e - s
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total -= min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return total


def _split_exchange(r, ops: list[trace.Op]) -> tuple[list, list]:
    """One chip's events in the traced solve: (the exchange's, the rest)."""
    mine, rest = [], []
    for o in trace.clip(ops, *r.window_ns):
        is_mine = in_program(r, o) and stage(r.hlo.find(o)) == EXCHANGE
        (mine if is_mine else rest).append(o)
    return mine, rest


def exposed_ms_per_iter(r) -> float | None:
    """Per iteration of the traced solve, in ms, on the chip where it is
    largest: the part of the halo exchange's events during which no other
    event ran on that chip.  None where no exchange ran."""
    if not _readable(r):
        return None
    split = [_split_exchange(r, ops) for ops in r.trace.devices.values()]
    if not any(mine for mine, _ in split):
        return None
    return max(uncovered_ns(mine, rest) for mine, rest in split) * 1e-6 / r.traced_iters


def report(r) -> dict:
    """Per chip, ms per iteration of the traced solve: each stage, the solve
    program's total and the exchange's exposed part; and the idle gaps
    between the solve program's first and last event, grouped by the stage
    of the program's event that ended last before each gap ([number,
    total ms])."""
    per = 1e-6 / r.traced_iters
    chips = {}
    for dev, by in stage_ns(r).items():
        ops = r.trace.devices[dev]
        own = sorted(solve_ops(r, ops), key=lambda o: o.end)
        ends = [o.end for o in own]
        gaps: dict = {}
        if own:
            lo = min(o.start for o in own)
            for s, e in trace.idle_gaps(trace.clip(ops, *r.window_ns), lo, ends[-1]):
                i = bisect.bisect_right(ends, s) - 1
                key = stage(r.hlo.find(own[i])) if i >= 0 else "(none)"
                n, ms = gaps.get(key, (0, 0.0))
                gaps[key] = (n + 1, ms + (e - s) * 1e-6)
        chips[dev] = {
            "ms_per_iter": {k: v * per for k, v in by.items()},
            "total_ms_per_iter": sum(by.values()) * per,
            "exchange_exposed_ms_per_iter": uncovered_ns(*_split_exchange(r, ops)) * per,
            "gaps": {k: list(v) for k, v in gaps.items()},
        }
    return chips
