"""From a jax profiler trace (``.xplane.pb``) to device busy time, per-op
time and named idle gaps.  Read with ``jax.profiler.ProfileData`` alone.

A TPU's plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per executed HLO instruction (name = the instruction's text), ``XLA
Modules`` one per executed program (``jit_<fn>(<hash>)``).  Host threads are
lines of ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` is an event on
the line of the thread that opened it.  All times are on one clock, in ns.
"""
import contextlib
import glob
import os
import re
import shutil

import numpy as np

WINDOW = "chipbench.window"
# host events that say what the host was doing: the harness's own phases and
# jax's per-call events, which carry the jitted function's name
_HOST_NAMES = ("chipbench.", "PjitFunction(")
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
                                  or e[2].startswith(_HOST_NAMES)]
        win = [e for e in self.host if e[2] == WINDOW]
        if not win:
            raise ValueError(f"{path} was not taken by capture(): it has no "
                             f"{WINDOW} annotation")
        self.t0, self.t1 = win[0][0], win[0][1]
        self.window_s = (self.t1 - self.t0) / 1e9
        self.first = min(self.devices, default=None)     # the first chip

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
        """[[name, seconds]] of what took most time on the first chip.
        Instructions that differ only in their numbers (``fusion.38`` and
        ``fusion.71``, same category and output) are summed under one name,
        ``fusion.N ... x<how many>``: a profile of a hundred like copies would
        otherwise show ten of them."""
        total, members = {}, {}
        for s, e, n in self.ops(self.first) if self.devices else []:
            key = re.sub(r"\.\d+", ".N", short_name(n, limit=10**6))
            total[key] = total.get(key, 0.0) + (e - s) / 1e9
            members.setdefault(key, set()).add(n)
        worst = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[(k if len(members[k]) == 1 else
                  f"{k[:57]} x{len(members[k])}")[:64], t] for k, t in worst]

    def idle_gaps(self, top=10):
        """[[host activity, seconds]]: the first chip's idle time summed by
        the innermost named host event over each gap's middle."""
        if not self.devices:
            return []
        _, merged = union_ns([(s, e) for s, e, _
                              in self.ops(self.first)])
        edges = np.concatenate([[self.t0], merged.ravel(), [self.t1]])
        host = [e for e in self.host if e[2] != WINDOW]
        total = {}
        for g0, g1 in edges.reshape(-1, 2):
            if g1 - g0 < MIN_GAP_NS:
                continue
            mid = (g0 + g1) / 2
            over = [e for e in host if e[0] <= mid < e[1]]
            name = min(over, key=lambda e: e[1] - e[0])[2] if over \
                else "unattributed"
            total[name] = total.get(name, 0.0) + float(g1 - g0) / 1e9
        worst = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[n[:64], float(t)] for n, t in worst]
