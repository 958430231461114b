"""From a jax profiler trace (``.xplane.pb``) to device busy time, SELF time
per op, scope and phase of the step, and named idle gaps.  Read with
``jax.profiler.ProfileData``; the instructions' ``jax.named_scope`` paths
come from the plane's event metadata (``xplane.py``).

A TPU's plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per executed HLO instruction (name = the instruction's text), ``XLA
Modules`` one per executed program (``jit_<fn>(<hash>)``).  Host threads are
lines of ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` is an event on
the line of the thread that opened it.  All times are on one clock, in ns.
The ``XLA Ops`` line holds a ``while`` (or a conditional) AND, nested inside
it, the instructions of its body: a sum over that line counts the loop
twice, so every per-op number here is SELF time, an event's duration less
the events nested in it.
"""
import contextlib
import glob
import os
import re
import shutil

import numpy as np

from chipbench.trace import xplane

WINDOW = "chipbench.window"
# host events that say what the host was doing: the harness's own phases,
# jax's per-call events, which carry the jitted function's name, and the
# program's spans (``profiler.scope``: ``<Component>.<phase>``, PERF.md 3)
_HOST_NAMES = ("chipbench.", "PjitFunction(")
SPAN = re.compile(r"^[A-Z]\w*\.\w+$")
MIN_GAP_NS = 20_000          # shorter gaps are launch latency between ops


@contextlib.contextmanager
def capture(out_dir):
    """Profile what runs inside, into a fresh ``out_dir``, under one
    ``chipbench.window`` annotation that marks the window."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(out_dir):
    files = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return files[-1]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def union_ns(intervals):
    """Total length of the union of (start, end) intervals, and the merged
    intervals as an [n, 2] array."""
    if len(intervals) == 0:
        return 0.0, np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), np.float64)
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])
    merged = np.stack([starts, ends[last]], axis=1)
    return float((merged[:, 1] - merged[:, 0]).sum()), merged


def short_name(hlo, limit=64):
    """``%fusion.9 = (.., bf16[256,56,56,256]{..}) fusion(..), kind=kOutput``
    -> ``fusion.9 kOutput bf16[256,56,56,256]``: the instruction's name, its
    category (fusion kind, custom-call target, else the opcode) and its
    largest output, at most ``limit`` characters."""
    m = re.match(r"%?([\w.\-]+) = (.*)", hlo, re.S)
    if not m:
        return hlo[:limit]
    name, rest = m.groups()
    rest = re.sub(r"\{[^{}]*\}", "", rest)      # layouts hold parentheses
    end = rest.find(" ")
    if rest.startswith("("):                    # a tuple of outputs
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    shapes = re.findall(r"\w+\[[\d,]*\]", rest[:end])
    shape = max(shapes, default="", key=lambda s: int(np.prod(
        [int(d) for d in re.findall(r"\d+", s[s.index("["):])] or [1])))
    cat = re.search(r'custom_call_target="([^"]+)"', rest) \
        or re.search(r"kind=(\w+)", rest) \
        or re.match(r"\s*([\w\-]+)\(", rest[end:])
    return " ".join(p for p in (name, cat.group(1) if cat else "", shape)
                    if p)[:limit]


def self_ns(events):
    """SELF time of each of ``events`` ((start, end, ...) on ONE line of a
    plane, in any order): its duration less the part that the events nested
    directly in it cover.  A ``while`` keeps only what its body's
    instructions do not cover; events that do not nest keep their own."""
    out = [float(e[1] - e[0]) for e in events]
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    stack = []                                  # indices of the open events
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:                               # nested in the innermost
            out[stack[-1]] -= min(e, events[stack[-1]][1]) - s
        stack.append(i)
    return out


PHASES = ("forward", "recompute", "backward", "optimizer", "other")
_OPTIMIZER = re.compile(r"(^|/)optimizer(/|$)")
_FORWARD = re.compile(r"(^|/)(jvp\()?(forward|loss)\)?(/|$)")


def phase_of(scope):
    """The phase of the training step an instruction belongs to, from its
    ``jax.named_scope`` path.  The order of the rules matters: a recomputed
    forward sits INSIDE the backward's ``transpose(jvp(forward))``, as
    ``.../checkpoint/rematted_computation/...``, so it is asked for first."""
    if not scope:
        return "other"
    if _OPTIMIZER.search(scope):
        return "optimizer"
    if "rematted_computation" in scope:
        return "recompute"
    if "transpose(" in scope:
        return "backward"
    if _FORWARD.search(scope):
        return "forward"
    return "other"


class Trace:
    """One trace, cut to its ``chipbench.window``."""

    def __init__(self, path):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.devices, self.host = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                self.devices[plane.name] = {l.name: _events(l)
                                            for l in plane.lines}
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    self.host += [e for e in _events(line)
                                  if e[2] == WINDOW
                                  or e[2].startswith(_HOST_NAMES)
                                  or SPAN.match(e[2])]
        win = [e for e in self.host if e[2] == WINDOW]
        if not win:
            raise ValueError(f"{path} was not taken by capture(): it has no "
                             f"{WINDOW} annotation")
        self.t0, self.t1 = win[0][0], win[0][1]
        self.window_s = (self.t1 - self.t0) / 1e9
        self.first = min(self.devices, default=None)     # the first chip
        # instruction text -> named_scope path, the first chip's
        self.scopes = {n: s.rstrip(":") for n, s in xplane.op_scopes(
            path).get(self.first, {}).items()} if self.devices else {}
        self._step_ops = None

    def ops(self, device=None):
        """(start, end, name) of the executed HLO instructions, clipped to
        the window: for sums of busy time.  A duration taken from one event
        needs the event whole, which one at the window's edge is not."""
        names = sorted(self.devices) if device is None else [device]
        out = []
        for d in names:
            for s, e, n in self.devices[d].get("XLA Ops", []):
                if e > self.t0 and s < self.t1:
                    out.append((max(s, self.t0), min(e, self.t1), n))
        return out

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_ns([(s, e) for s, e, _ in self.ops(d)])[0]
                   for d in self.devices) / len(self.devices) / 1e9

    def device_ops(self, top=10):
        """[[name, seconds]] of what took most SELF time on the first chip.
        Instructions that differ only in their numbers (``fusion.38`` and
        ``fusion.71``, same category and output) are summed under one name,
        ``fusion.N ... x<how many>``: a profile of a hundred like copies would
        otherwise show ten of them."""
        total, members, keys = {}, {}, {}
        ops = self.ops(self.first) if self.devices else []
        for (_, _, n), own in zip(ops, self_ns(ops)):
            if n not in keys:               # an instruction runs every step
                keys[n] = re.sub(r"\.\d+", ".N", short_name(n, limit=10**6))
            key = keys[n]
            total[key] = total.get(key, 0.0) + own / 1e9
            members.setdefault(key, set()).add(n)
        worst = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[(k if len(members[k]) == 1 else
                  f"{k[:57]} x{len(members[k])}")[:64], t] for k, t in worst]

    def whole_steps(self):
        """[(start, end)] of the step program's executions on the first chip
        that lie WHOLLY inside the window, in order.  The step program is the
        ``XLA Modules`` name with the most device time; the window's edges
        (``trace_s`` is no multiple of a step) cut the first and the last.
        The trace's first execution is never counted: the step that was
        running when the profiler started shows as an event that begins
        where the trace does, short by what ran before."""
        by_name = {}
        for s, e, n in self.devices.get(self.first, {}).get("XLA Modules", []):
            by_name.setdefault(n, []).append((s, e))
        if not by_name:
            return []
        runs = max(by_name.values(), key=lambda v: sum(
            min(e, self.t1) - max(s, self.t0) for s, e in v
            if e > self.t0 and s < self.t1))
        return [(s, e) for s, e in sorted(runs)[1:]
                if s >= self.t0 and e <= self.t1]

    def step_ops(self):
        """One record for each instruction executed inside a whole step:
        ``(step index, SELF ns, instruction name as short_name gives it,
        named_scope path or "", phase)``."""
        if self._step_ops is None:
            steps = np.asarray(self.whole_steps(), np.float64).reshape(-1, 2)
            ops = self.devices.get(self.first, {}).get("XLA Ops", [])
            # the step that holds an instruction's start, if it holds its end
            at = np.searchsorted(steps[:, 0], [s for s, _, _ in ops],
                                 side="right") - 1
            inside = [(s, e, n, int(i)) for (s, e, n), i in zip(ops, at)
                      if i >= 0 and e <= steps[i, 1]]
            names = {}                      # an instruction runs every step

            def named(n):
                if n not in names:
                    scope = self.scopes.get(n, "")
                    names[n] = (short_name(n), scope, phase_of(scope))
                return names[n]
            self._step_ops = [(i, own) + named(n) for (_, _, n, i), own
                              in zip(inside, self_ns(inside))]
        return self._step_ops

    def gaps(self):
        """[(start, end, host activity)] of the first chip's idle intervals
        of MIN_GAP_NS or more inside the window, each named by the innermost
        named host event over its middle."""
        if not self.devices:
            return []
        _, merged = union_ns([(s, e) for s, e, _
                              in self.ops(self.first)])
        edges = np.concatenate([[self.t0], merged.ravel(), [self.t1]])
        host = [e for e in self.host if e[2] != WINDOW]
        out = []
        for g0, g1 in edges.reshape(-1, 2):
            if g1 - g0 < MIN_GAP_NS:
                continue
            mid = (g0 + g1) / 2
            over = [e for e in host if e[0] <= mid < e[1]]
            out.append((float(g0), float(g1),
                        min(over, key=lambda e: e[1] - e[0])[2] if over
                        else "unattributed"))
        return out

    def idle_gaps(self, top=10):
        """[[host activity, seconds]]: the first chip's idle time summed by
        the innermost named host event over each gap's middle."""
        total = {}
        for g0, g1, name in self.gaps():
            total[name] = total.get(name, 0.0) + (g1 - g0) / 1e9
        worst = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[n[:64], float(t)] for n, t in worst]

    def spans(self, name):
        """Durations in ms of the host events called ``name`` that lie
        wholly inside the window."""
        return [(e - s) / 1e6 for s, e, n in self.host
                if n == name and s >= self.t0 and e <= self.t1]
