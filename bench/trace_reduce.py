"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metric readers take.

What is read:

* every device plane (``/device:TPU:<i>``) and its ``XLA Ops`` line: the
  operations that ran on that chip, each with its start and duration; and
  its ``Async XLA Ops`` line, where an asynchronous collective shows its
  whole flight from -start to -done;
* the host plane's ``jax.profiler.TraceAnnotation`` spans that the
  benchmark writes around its own calls (``step``, ``batch``,
  ``dispatch``, ``wait``), on whichever thread line they are.

What comes out (``reduce_events``), per device and averaged over devices:

* ``busy_s``: the union of the intervals in which an operation ran;
* ``window_s``: the traced window, given by the caller as the host span
  that encloses the measured steps (or, failing that, first op start to
  last op end over all devices);
* ``op_s``: device self time per operation name (the HLO instruction
  name, e.g. ``fusion.3``), summed over its events: a ``while`` loop's
  event encloses the events of its body, so each event is charged only
  the time no enclosed event covers, and the op times add up to busy;
* ``loop_s``: the enclosing time of each ``while`` loop (a scan over
  layers or microbatches), inclusive of its body;
* ``collective_s``: the union of the intervals of collective operations
  (all-reduce, all-gather, all-to-all, reduce-scatter, collective-permute,
  send/recv, and their -start/-done halves), on either line;
* ``exposed_collective_s``: the part of ``collective_s`` in which no other
  operation ran on that device;
* ``idle_gaps``: the longest gaps between busy intervals inside the
  window, each with the host annotation that covers most of it.

Device and host timestamps share the profiler's clock, up to about a
millisecond of skew, so the attribution of gaps shorter than that is
approximate.
"""
from __future__ import annotations

import functools
import re
from typing import Iterable

COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast|send|recv|ragged-all-to-all)(-start|-done)?\b")
HOST_SPANS = ("step", "batch", "dispatch", "wait")


# a trace repeats each instruction's text once a step: parse each text once
@functools.lru_cache(maxsize=None)
def op_name(hlo_text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = hlo_text.split(" = ", 1)[0]
    return head.lstrip("%").strip()


@functools.lru_cache(maxsize=None)
def op_code(hlo_text: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``fusion``, ``all-gather``,
    ...); the instruction name when the text has no ``=``."""
    if " = " not in hlo_text:
        return op_name(hlo_text)
    rhs = hlo_text.split(" = ", 1)[1]
    # the result shape comes first: a type, or a tuple in parentheses
    depth, i = 0, 0
    while i < len(rhs):
        c = rhs[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    m = re.match(r"\s*([\w\-]+)\(", rhs[i:])
    return m.group(1) if m else op_name(hlo_text)


@functools.lru_cache(maxsize=None)
def is_collective(hlo_text: str) -> bool:
    return bool(COLLECTIVE_RE.match(op_code(hlo_text))
                or COLLECTIVE_RE.match(op_name(hlo_text)))


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


MIN_GAP_NS = 1000.0


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of [lo, hi] between the disjoint sorted ``busy``,
    leaving out those under a microsecond (back-to-back operations whose
    recorded ends and starts do not quite meet)."""
    return [g for g in subtract([(lo, hi)], clip(busy, lo, hi))
            if g[1] - g[0] >= MIN_GAP_NS]


def load(path: str, spans: tuple[str, ...] = HOST_SPANS) -> dict:
    """Events of a trace file: {"devices": {name: [(text, start_ns,
    dur_ns)]}, "async": {name: [...]}, "host": [(name, start_ns, dur_ns)]}
    with host spans limited to those named in ``spans`` (the benchmark's
    annotations)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, asyncs, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                dest = {"XLA Ops": devices,
                        "Async XLA Ops": asyncs}.get(line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
        elif plane.name == "/host:CPU":
            # the main thread's line is named after the process
            # ("python", "python3"): take the spans from any line
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name in spans)
    return {"devices": devices, "async": asyncs, "host": host}


def self_times(evs) -> list[tuple[str, float, float, float]]:
    """(text, start, duration, self time) of events on one line, where
    self time leaves out the events an event encloses (a loop's body)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    child = [0.0] * len(evs)
    stack: list[int] = []
    for i in order:
        _, s, d = evs[i]
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += d
        stack.append(i)
    return [(t, s, d, max(d - child[i], 0.0))
            for i, (t, s, d) in enumerate(evs)]


def attribute(gap: tuple[float, float], host) -> str:
    """The host annotation that overlaps ``gap`` most; among spans that
    overlap equally, the innermost (shortest). "none" when nothing does."""
    best, best_key = "none", (0.0, 0.0)
    for name, s, d in host:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov <= 0:
            continue
        key = (ov, -d)
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_events(ev: dict, window: tuple[float, float] | None = None,
                  top: int = 10) -> dict:
    """The reduction of :func:`load`'s events. ``window`` is (start_ns,
    end_ns) on the trace clock; without it the window runs from the first
    device op to the last."""
    devs = {k: v for k, v in ev["devices"].items() if v}
    if not devs:
        return {"n_devices": 0}
    if window is None:
        lo = min(s for v in devs.values() for _, s, _ in v)
        hi = max(s + d for v in devs.values() for _, s, d in v)
    else:
        lo, hi = window
    per = {}
    op_s: dict[str, float] = {}
    loop_s: dict[str, float] = {}
    all_gaps = []
    for name, evs in sorted(devs.items()):
        busy = clip(union((s, s + d) for _, s, d in evs), lo, hi)
        flights = ev.get("async", {}).get(name, [])
        coll = clip(union((s, s + d) for t, s, d in evs + flights
                          if is_collective(t)), lo, hi)
        comp = union((s, s + d) for t, s, d in evs if not is_collective(t))
        exposed = subtract(coll, comp)
        for t, s, d, own in self_times(evs):
            if s + d <= lo or s >= hi:
                continue
            op = op_name(t)
            op_s[op] = op_s.get(op, 0.0) + own * 1e-9 / len(devs)
            if op_code(t) == "while":
                loop_s[op] = loop_s.get(op, 0.0) + d * 1e-9 / len(devs)
        per[name] = {"busy_s": total(busy) * 1e-9,
                     "collective_s": total(coll) * 1e-9,
                     "exposed_collective_s": total(exposed) * 1e-9}
        if not all_gaps:   # gaps of the first device stand for all
            all_gaps = gaps(busy, lo, hi)
    n = len(per)
    mean = {k: sum(p[k] for p in per.values()) / n
            for k in ("busy_s", "collective_s", "exposed_collective_s")}
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "n_devices": n,
        "window_s": (hi - lo) * 1e-9,
        **mean,
        "per_device": per,
        "op_s": dict(sorted(op_s.items(), key=lambda kv: -kv[1])),
        "loop_s": dict(sorted(loop_s.items(), key=lambda kv: -kv[1])),
        "idle_gaps": [[attribute(g, ev["host"]), (g[1] - g[0]) * 1e-9]
                      for g in longest],
    }


def host_window(ev: dict, name: str = "step") -> tuple[float, float] | None:
    """(start, end) of the host spans called ``name``: first start to last
    end, the traced window of the steps."""
    spans = [(s, s + d) for n, s, d in ev["host"] if n == name]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)
