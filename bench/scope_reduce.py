"""The program's own marks in a JAX profiler trace: device time per named
scope of the train step, and the ``train/*`` host spans of
``Trainer.run``. Adds to what ``bench/trace_reduce.py`` gives:

* ``scope_s``: device self time per scope of ``repro.obs.scopes``
  (``model``, ``sync``, ``sync/compress``, ``sync/qsgd``, ``update``),
  each op charged to its innermost scope through the compiled step's
  ``{instruction: scope}`` map (``repro.obs.scopes.op_scopes``), inside
  the window and averaged over devices, as ``op_s`` counts;
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

The readers ``bench/metrics/{model,compress,qsgd,update,host_step}_ms.py``
take these keys; ``bench/tools/scope_trace.py`` writes them on the chip.
"""
from __future__ import annotations

from bench import trace_reduce as tr

PROGRAM_SPANS = ("train/run", "train/batch", "train/dispatch", "train/wait",
                 "train/log", "train/checkpoint")


def load_program_spans(path: str) -> list[tuple[str, float, float]]:
    """(name, start_ns, dur_ns) of the program's ``train/*`` spans on the
    host plane of a trace file, from any thread line."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name in PROGRAM_SPANS]


def scope_times(devices: dict, op_scope: dict[str, str],
                window: tuple[float, float]) -> dict:
    """``scope_s`` and ``unscoped_s`` of the device events ``devices``
    ({device: [(hlo text, start_ns, dur_ns)]}, as ``trace_reduce.load``
    gives them) in ``window``; ``op_scope`` maps instruction names to
    scopes."""
    devs = {k: v for k, v in devices.items() if v}
    lo, hi = window
    scope_s: dict[str, float] = {}
    unscoped = 0.0
    for evs in devs.values():
        for t, s, d, own in tr.self_times(evs):
            if s + d <= lo or s >= hi:
                continue
            sec = own * 1e-9 / len(devs)
            name = op_scope.get(tr.op_name(t))
            if name is None:
                unscoped += sec
            else:
                scope_s[name] = scope_s.get(name, 0.0) + sec
    return {"scope_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
            "unscoped_s": unscoped}


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
    by_span: dict[str, float] = {}
    named = []
    for g in all_gaps:
        split = innermost_time(g, spans)
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
        red.update(scope_times(devs, op_scope, window))
        red.update(idle_names(devs, list(ev["host"]) + list(spans), window,
                              top=top))
    return red
