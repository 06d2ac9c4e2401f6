"""From a profiler trace to busy time, idle gaps, time by operation and
exposed collective time.

The reduction works on a plain structure, so that the test can feed it a
recorded trace: ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``. ``load_xplane`` makes that structure
from the ``.xplane.pb`` file the JAX profiler writes.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
ANNOTATION = re.compile(r"^bench\.")


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTRUCTION = re.compile(r"%?([\w.\-]+) = (.*?) ?([\w\-]+)\(")


def short_name(text: str) -> str:
    """The trace names a device operation by its whole HLO instruction. Keep
    its name, its opcode and its result type without layouts; of a Mosaic
    kernel also how many operands it takes (which tells the kernels of one
    module apart)."""
    bare = _LAYOUT.sub("", _LAYOUT.sub("", text))
    m = _INSTRUCTION.match(bare)
    if not m:
        return bare[:120]
    name, result, opcode = m.groups()
    if 'custom_call_target="tpu_custom_call"' in text:
        constraints = text.partition("operand_layout_constraints={")[2] \
            .partition("frontend_attributes")[0]
        operands = len(re.findall(r"[a-z]+[0-9]*\[", constraints))
        return f"{name} tpu_custom_call {result} operands={operands}"
    return f"{name} {opcode} {result}"[:120]


def load_xplane(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes, seen = [], {}
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            seen.setdefault(plane.name, []).append(line.name)
            if device and line.name != OPS_LINE:
                continue
            events = [[short_name(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or ANNOTATION.match(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "lines_seen": seen}


def union(intervals: list) -> list:
    """Sorted disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def measure(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: list, b: list) -> list:
    """Points of the disjoint sorted intervals ``a`` not in ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append([cur, hi])
    return out


def self_intervals(events: list) -> list:
    """``[(name, intervals)]``: each event's interval less its direct
    children's (an operation that encloses others, as a loop does its body,
    is not counted twice, and does not hide what runs inside it)."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]][2].append([start, start + dur])
        out.append([name, [start, start + dur], []])
        stack.append((len(out) - 1, start + dur))
    return [(name, subtract([own], union(children)))
            for name, own, children in out]


def _device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _annotations(trace: dict) -> list:
    return sorted((e[1], e[1] + e[2], e[0]) for p in trace["planes"]
                  if not DEVICE_PLANE.match(p["name"])
                  for line in p["lines"] for e in line["events"]
                  if ANNOTATION.match(e[0]))


def _gap_owner(gap: list, annotations: list) -> str:
    best, best_overlap = "between_annotations", 0.0
    for a, b, name in annotations:
        overlap = min(b, gap[1]) - max(a, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce_trace(trace: dict) -> dict:
    """Seconds, averaged over the device planes found:

    - ``window_s``: first operation's start to last operation's end;
    - ``busy_s``: union of the operations' intervals;
    - ``ops``: {name: [calls, self seconds]};
    - ``collective_s``: union of collective operations' intervals;
      ``collective_exposed_s``: the part of it in which no other operation
      runs on that device;
    - ``idle_gaps``: {host annotation that covers most of the gap: seconds}.
    """
    devices = _device_planes(trace)
    if not devices:
        return {}
    annotations = _annotations(trace)
    n = len(devices)
    out = {"devices": n, "lines_seen": trace.get("lines_seen"),
           "window_s": 0.0, "busy_s": 0.0, "collective_s": 0.0,
           "collective_exposed_s": 0.0, "ops": {}, "idle_gaps": {}}
    for plane in devices:
        events = [e for line in plane["lines"] for e in line["events"]]
        if not events:
            return {}
        own = self_intervals(events)
        busy = union([[e[1], e[1] + e[2]] for e in events])
        coll = union([i for name, iv in own if COLLECTIVE.match(name)
                      for i in iv])
        compute = union([i for name, iv in own if not COLLECTIVE.match(name)
                         for i in iv])
        window = [busy[0][0], busy[-1][1]]
        out["window_s"] += (window[1] - window[0]) / 1e9 / n
        out["busy_s"] += measure(busy) / 1e9 / n
        out["collective_s"] += measure(coll) / 1e9 / n
        out["collective_exposed_s"] += measure(subtract(coll, compute)) \
            / 1e9 / n
        for name, iv in own:
            calls, total = out["ops"].get(name, (0, 0.0))
            out["ops"][name] = [calls + 1.0 / n,
                                total + measure(iv) / 1e9 / n]
        for gap in subtract([window], busy):
            owner = _gap_owner(gap, annotations)
            out["idle_gaps"][owner] = out["idle_gaps"].get(owner, 0.0) \
                + (gap[1] - gap[0]) / 1e9 / n
    return out


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: heaviest operations, longest gaps."""
    ops = sorted(((name, v[1]) for name, v in reduced.get("ops", {}).items()),
                 key=lambda t: -t[1])[:top]
    gaps = sorted(reduced.get("idle_gaps", {}).items(),
                  key=lambda t: -t[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}
