"""The reduction from a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
per TPU device, every operation of its "XLA Ops" line (the operations the
core runs in order; a ``while`` spans the operations of its body) and of
its "Async XLA Ops" line (copies and collectives in flight), each with
its start, duration, HLO instruction name and name stack, and from the
host the spans of the traced steps (``StepTraceAnnotation("train")``)
and the events the host threads ran.  The trace's operations carry no
name stack on the TPU, so it is looked up by instruction name in the
compiled step's HLO text (``metadata={op_name=...}``).  The window is
the first traced step's start to the last one's end.  The reduced trace
is a plain dict, so a small one recorded on the chip can be kept as JSON
and replayed by the tests.

Busy time is the union of the "XLA Ops" intervals.  A class's time is
the self time of its operations on that line (a ``while`` counts only
the time its body's operations leave uncovered) plus, for collectives,
the async line's collective spans.

Classes of device operations (``classify``):

* ``collective``: all-reduce, all-gather, reduce-scatter,
  collective-permute and all-to-all, their async start and done too;
* ``fwd_bwd``: operations whose name stack holds ``jvp(``, the forward
  and backward of ``value_and_grad``;
* ``post_grad``: the rest, the compressor's local work and the
  optimizer.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?([.-]|$)")
STEP_NAME = "train"
DEVICE_PLANE = re.compile(r"/device:TPU:\d+")


def classify(name: str, stack: str) -> str:
    if COLLECTIVE.match(name):
        return "collective"
    if "jvp(" in stack:
        return "fwd_bwd"
    return "post_grad"


def _union_ns(intervals, lo: int, hi: int) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _self_times(ops):
    """Self time of each operation of a properly nested timeline."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    self_t = [float(op[1]) for op in ops]
    stack = []                                   # indices of open ops
    for i in order:
        s, d = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= d
        stack.append(i)
    return [max(t, 0.0) for t in self_t]


class Trace:
    """A reduced trace: ``{"window": [lo, hi], "devices": {name: [[start,
    dur, op, stack, line], ...]}, "host": [[start, dur, name, thread],
    ...]}`` with times in nanoseconds on the profiler's clock and ``line``
    "sync" (XLA Ops) or "async" (Async XLA Ops)."""

    def __init__(self, data: dict):
        self.data = data
        self.window = tuple(data["window"])
        self.devices = data["devices"]
        self.host = data["host"]

    # -- what the metrics read ----------------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _sync(self, ops):
        return [op for op in ops if op[4] == "sync"]

    def busy_s(self) -> float:
        """Union of the device's operation intervals in the window,
        averaged over the devices."""
        lo, hi = self.window
        per = [_union_ns([(s, s + d) for s, d, *_ in self._sync(ops)],
                         lo, hi)
               for ops in self.devices.values()]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def self_ns(self):
        """{device: [(op, self ns inside the window)]}, each op once."""
        lo, hi = self.window
        out = {}
        for dev, ops in self.devices.items():
            sync = [op for op in self._sync(ops)
                    if op[0] < hi and op[0] + op[1] > lo]
            clipped = [[max(s, lo), min(s + d, hi) - max(s, lo), *rest]
                       for s, d, *rest in sync]
            pairs = list(zip(clipped, _self_times(clipped)))
            pairs += [(op, min(op[0] + op[1], hi) - max(op[0], lo))
                      for op in ops if op[4] == "async"
                      and op[0] < hi and op[0] + op[1] > lo
                      and classify(op[2], op[3]) == "collective"]
            out[dev] = pairs
        return out

    def class_ns(self, cls: str) -> float:
        """Device nanoseconds of one class of operations inside the
        window, averaged over the devices."""
        per = [sum(t for op, t in pairs if classify(op[2], op[3]) == cls)
               for pairs in self.self_ns().values()]
        return sum(per) / len(per) if per else 0.0

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (averaged over the
        devices) and the longest idle gaps of the first device, each
        named by the host event that was running in it."""
        lo, hi = self.window
        agg: Dict[str, float] = {}
        for pairs in self.self_ns().values():
            for op, t in pairs:
                where = op[3].rsplit("/", 1)[-1] if op[3] else ""
                key = f"{op[2]} {where} [{classify(op[2], op[3])}]"
                agg[key] = agg.get(key, 0.0) + t
        n = max(len(self.devices), 1)
        device_ops = sorted(([k, v / n / 1e9] for k, v in agg.items()),
                            key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.devices:
            ops = sorted(self._sync(next(iter(self.devices.values()))))
            cur = lo
            for s, d, *_ in ops:
                if s > cur and s > lo:
                    gaps.append((max(cur, lo), min(s, hi)))
                cur = max(cur, s + d)
                if cur >= hi:
                    break
            if cur < hi:
                gaps.append((cur, hi))
        gaps = sorted((g for g in gaps if g[1] > g[0]),
                      key=lambda g: g[0] - g[1])[:top]
        idle = [[self.host_at((a + b) // 2), (b - a) / 1e9] for a, b in gaps]
        return {"device_ops": device_ops, "idle_gaps": idle}

    def host_at(self, t: int) -> str:
        """The shortest host event that spans ``t`` (the innermost)."""
        best: Optional[Tuple[int, str]] = None
        for s, d, name, _thread in self.host:
            if s <= t <= s + d and name != STEP_NAME and \
                    (best is None or d < best[0]):
                best = (d, name)
        return best[1] if best else "no host event"

    def to_json(self, path: str):
        with gzip.open(path, "wt") as f:
            json.dump(self.data, f)


def from_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace(json.load(f))


INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, str]:
    """{HLO instruction name: name stack} from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if m:
            n = OP_NAME.search(line)
            out[m.group(1)] = n.group(1) if n else ""
    return out


def _instr(event_name: str) -> str:
    m = INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


LINES = {"XLA Ops": "sync", "Async XLA Ops": "async"}


def load(trace_dir: str, hlo_text: str = "") -> Trace:
    """Reduce the profiler's ``.xplane.pb`` under ``trace_dir``; name
    stacks come from ``hlo_text``, the compiled step's HLO."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {paths}")
    stacks = op_names(hlo_text)
    pd = ProfileData.from_file(paths[0])
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    steps: List[Tuple[int, int]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            for line in plane.lines:
                kind = LINES.get(line.name)
                if kind is None:
                    continue
                for e in line.events:
                    name = _instr(e.name)
                    devices.setdefault(plane.name, []).append(
                        [e.start_ns, e.duration_ns, name,
                         stacks.get(name, ""), kind])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == STEP_NAME:
                        steps.append((e.start_ns, e.end_ns))
                    host.append([e.start_ns, e.duration_ns, e.name,
                                 line.name])
    if not steps:
        raise RuntimeError("no traced step spans in the trace")
    window = [min(s for s, _ in steps), max(e for _, e in steps)]
    host = [h for h in host if h[0] < window[1] and h[0] + h[1] > window[0]]
    for ops in devices.values():
        ops.sort()
    return Trace({"window": window, "devices": devices, "host": host})
