"""The program's own marks in a JAX profiler trace: device time per named
scope of the train step, and the ``train/*`` host spans of
``Trainer.run``. Adds to what ``bench/trace_reduce.py`` gives:

* ``scope_s``: device self time per scope of ``repro.obs.scopes``
  (``model``, ``sync``, ``sync/compress``, ``sync/qsgd``, ``update``),
  each op charged to its innermost scope through the compiled step's
  ``{instruction: scope}`` map (``repro.obs.scopes.op_scopes``), inside
  the window and averaged over devices, as ``op_s`` counts;
* ``scope_incl_s``: each scope's self time plus that of every scope
  nested under its name (``model`` holds ``model/attention``; ``sync``
  holds ``sync/compress`` and ``sync/qsgd``), so a reader of a scope keeps
  its meaning when a later step names parts inside it;
* ``unscoped_s``: the rest of that self time, ops no scope names;
* ``host_span_s`` and ``host_span_n``: total length (clipped to the
  window) and count of each ``train/*`` span inside the window.

The program's spans also join the benchmark's (``step``, ``batch``,
``dispatch``) as names for the idle gaps. ``trace_reduce.attribute``
names a gap by the span that overlaps it most, then the innermost; but a
span overlaps a gap at least as much as any span inside it, so that rule
names by the outermost span, and the benchmark's ``step``, which
encloses ``train/run`` and the window loop's own bookkeeping, wins every
gap that reaches past ``train/run``. Here each instant of a gap belongs
to the innermost span open at it, and the gap takes the name that holds
most of its time:

* ``idle_gaps``: the longest gaps, each [name, seconds], so named;
* ``idle_by_span``: the whole idle time of the window split the same way,
  instant by instant, over every gap ("none" where no span is open).

``bench/run.py --trace 1`` reads its trace with :func:`load` and reduces
its window with :func:`reduce_with_program`; the readers
``bench/metrics/{model,compress,qsgd,update,host_step}_ms.py`` take these
keys.
"""
from __future__ import annotations

import bisect

from bench import trace_reduce as tr

PROGRAM_SPANS = ("train/run", "train/batch", "train/dispatch", "train/wait",
                 "train/log", "train/checkpoint")


def load(path: str) -> tuple[dict, list[tuple[str, float, float]]]:
    """The trace file read once: ``trace_reduce.load``'s events, and the
    (name, start_ns, dur_ns) of the program's ``train/*`` spans on the
    host plane, from any thread line."""
    ev = tr.load(path, tr.HOST_SPANS + PROGRAM_SPANS)
    spans = [h for h in ev["host"] if h[0] in PROGRAM_SPANS]
    ev["host"] = [h for h in ev["host"] if h[0] in tr.HOST_SPANS]
    return ev, spans


def scope_times(op_s: dict[str, float], op_scope: dict[str, str]) -> dict:
    """``scope_s``, ``scope_incl_s`` and ``unscoped_s`` from the device
    self time per instruction, ``op_s`` of ``trace_reduce.reduce_events``
    (inside the window, averaged over devices); ``op_scope`` maps
    instruction names to scopes."""
    scope_s: dict[str, float] = {}
    unscoped = 0.0
    for op, sec in op_s.items():
        name = op_scope.get(op)
        if name is None:
            unscoped += sec
        else:
            scope_s[name] = scope_s.get(name, 0.0) + sec
    return {"scope_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
            "scope_incl_s": inclusive(scope_s), "unscoped_s": unscoped}


def inclusive(scope_s: dict[str, float]) -> dict[str, float]:
    """Each scope and each enclosing name (``a`` and ``a/b`` of
    ``a/b/c``) with the self time of every scope at or under it."""
    out: dict[str, float] = {}
    for name, secs in scope_s.items():
        parts = name.split("/")
        for i in range(1, len(parts) + 1):
            key = "/".join(parts[:i])
            out[key] = out.get(key, 0.0) + secs
    return dict(sorted(out.items()))


def host_span_times(spans, window: tuple[float, float]) -> dict:
    """``host_span_s`` (length clipped to the window) and ``host_span_n``
    of each span name that overlaps the window."""
    lo, hi = window
    secs: dict[str, float] = {}
    count: dict[str, int] = {}
    for name, s, d in spans:
        ov = min(hi, s + d) - max(lo, s)
        if ov <= 0:
            continue
        secs[name] = secs.get(name, 0.0) + ov * 1e-9
        count[name] = count.get(name, 0) + 1
    return {"host_span_s": secs, "host_span_n": count}


def innermost_time(gap: tuple[float, float], spans) -> dict[str, float]:
    """Seconds of ``gap`` by the innermost (shortest) span open at each
    instant; "none" for instants no span covers."""
    lo, hi = gap
    over = [(n, s, s + d) for n, s, d in spans if s < hi and s + d > lo]
    cuts = sorted({lo, hi} | {t for _, s, e in over for t in (s, e)
                              if lo < t < hi})
    out: dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(e - s, n) for n, s, e in over if s <= a and e >= b]
        name = min(open_)[1] if open_ else "none"
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def idle_names(devices: dict, spans, window: tuple[float, float],
               top: int = 10) -> dict:
    """``idle_gaps`` and ``idle_by_span`` over the gaps of the first
    device that has any, as ``trace_reduce.reduce_events`` takes them."""
    lo, hi = window
    all_gaps: list = []
    for _, evs in sorted(devices.items()):
        busy = tr.clip(tr.union((s, s + d) for _, s, d in evs), lo, hi)
        all_gaps = tr.gaps(busy, lo, hi)
        if all_gaps:
            break
    # a span open in a gap starts less than the longest span before its end
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    longest_span = max((d for _, _, d in spans), default=0.0)
    by_span: dict[str, float] = {}
    named = []
    for g in all_gaps:
        near = spans[bisect.bisect_left(starts, g[0] - longest_span):
                     bisect.bisect_left(starts, g[1])]
        split = innermost_time(g, near)
        for n, secs in split.items():
            by_span[n] = by_span.get(n, 0.0) + secs
        named.append((max(split.items(), key=lambda kv: kv[1])[0], g))
    longest = sorted(named, key=lambda ng: ng[1][0] - ng[1][1])[:top]
    return {"idle_gaps": [[n, (g[1] - g[0]) * 1e-9] for n, g in longest],
            "idle_by_span": dict(sorted(by_span.items(),
                                        key=lambda kv: -kv[1]))}


def reduce_with_program(ev: dict, spans, op_scope: dict[str, str],
                        top: int = 10) -> dict:
    """``trace_reduce.reduce_events`` over the window of the ``step``
    spans, with the idle gaps named as above among the benchmark's and
    the program's spans, plus the keys above."""
    window = tr.host_window(ev)
    red = tr.reduce_events(ev, window, top=top)
    if window is None:
        return red
    red.update(host_span_times(spans, window))
    if red.get("n_devices"):
        devs = {k: v for k, v in ev["devices"].items() if v}
        red.update(scope_times(red["op_s"], op_scope))
        red.update(idle_names(devs, list(ev["host"]) + list(spans), window,
                              top=top))
    return red
