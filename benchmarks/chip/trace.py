"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle time, time per device operation,
collective time that no other operation overlaps, and idle gaps named by
the harness span the host was in.

Each TPU chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per operation run, timed from the profile's start
(``profile_start_time``, wall clock ns).  The harness's own spans
(``bench.fit`` around ``Federation.fit(1)``, ``bench.sync`` around the
wait for the server state) are taken on the wall clock and put on the
same time base.  The traced window runs from the first span's start to
the last span's end.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# HLO collectives, and their asynchronous start/done halves
COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv)")


@dataclasses.dataclass
class Ops:
    """One device's operations in the window: start and end (ns), name."""
    start: np.ndarray
    end: np.ndarray
    names: List[str]


def _union(start: np.ndarray, end: np.ndarray) -> List[Tuple[int, int]]:
    """Merged intervals of the union of ``[start, end)``."""
    out: List[List[int]] = []
    for s, e in sorted(zip(start.tolist(), end.tolist())):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Reduced:
    t0: int
    t1: int
    devices: List[Ops]
    spans: List[Tuple[int, int, str]]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_ns(self, d: Ops) -> int:
        return sum(e - s for s, e in _union(d.start, d.end))

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([self.busy_ns(d) for d in self.devices])) * 1e-9

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Summed device seconds of the operations ``match`` accepts,
        over every device."""
        return float(sum(e - s for d in self.devices
                         for s, e, n in zip(d.start, d.end, d.names)
                         if match(n))) * 1e-9

    def op_totals(self) -> Dict[str, float]:
        """Seconds per operation name, averaged over the devices."""
        tot: Dict[str, float] = {}
        for d in self.devices:
            for s, e, n in zip(d.start.tolist(), d.end.tolist(), d.names):
                tot[n] = tot.get(n, 0.0) + (e - s) * 1e-9
        k = max(1, len(self.devices))
        return {n: v / k for n, v in tot.items()}

    def exposed_collective_s(self) -> List[float]:
        """Per device: seconds in which a collective runs and no other
        operation does."""
        out = []
        for d in self.devices:
            coll = np.asarray([bool(COLLECTIVE.match(n)) for n in d.names])
            if not coll.any():
                out.append(0.0)
                continue
            other = _union(d.start[~coll], d.end[~coll])
            exposed = 0
            for s, e in _union(d.start[coll], d.end[coll]):
                covered = sum(max(0, min(e, oe) - max(s, os_))
                              for os_, oe in other)
                exposed += (e - s) - covered
            out.append(exposed * 1e-9)
        return out

    def gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps of every device, each named by the innermost harness
        span that holds its midpoint, longest first."""
        out = []
        for d in self.devices:
            busy = _union(d.start, d.end)
            edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    out.append((self.span_at((a + b) // 2), (b - a) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def span_at(self, t: int) -> str:
        inner = [(e - s, n) for s, e, n in self.spans if s <= t < e]
        return min(inner)[1] if inner else "outside bench spans"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_totals().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[short_name(n), s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in self.gaps()[:top]]}


def short_name(op: str) -> str:
    """``%round_step.1 = f32[7256,2048]{...} custom-call(...)`` ->
    ``round_step.1 custom-call f32[7256,2048]``; a tuple result reads
    ``(...)``."""
    name, sep, rest = op.partition(" = ")
    if not sep or not name.startswith("%"):
        return op[:80]
    if rest.startswith("("):             # a tuple: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        out, rest = "(...)", rest[i + 1:]
    else:
        out, _, rest = rest.partition(" ")
        out = out.split("{")[0]
    kind = rest.strip().split("(")[0]
    return f"{name[1:]} {kind} {out}"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def reduce(path: str, spans: Sequence[Tuple[int, int, str]],
           n_devices: int = 0) -> Reduced:
    """Read ``path`` (``.xplane.pb``, or gzipped ``.xplane.pb.gz``); keep
    the first ``n_devices`` TPU planes (all when 0), put the harness
    ``spans`` (wall clock ns) on the trace's time base, and clip
    operations to the window they cover."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    start, dev = None, {}
    for plane in pd.planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                start = int(v)
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                dev[int(m.group(1))] = [(e.start_ns, e.end_ns, e.name)
                                        for e in line.events]
    if start is None or not spans:
        raise ValueError(f"{path}: no profile start time, or no spans")
    spans = [(int(s) - start, int(e) - start, n) for s, e, n in spans]
    t0 = min(s for s, _, _ in spans)
    t1 = max(e for _, e, _ in spans)
    ids = sorted(dev)[: n_devices or None]
    devices = []
    for i in ids:
        ev = [(max(s, t0), min(e, t1), n) for s, e, n in dev[i]
              if e > t0 and s < t1]
        devices.append(Ops(np.asarray([int(s) for s, _, _ in ev], np.int64),
                           np.asarray([int(e) for _, e, _ in ev], np.int64),
                           [n for _, _, n in ev]))
    return Reduced(t0, t1, devices, spans)


def matcher(patterns: Sequence[str]) -> Callable[[str], bool]:
    """An operation-name predicate from regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return lambda name: any(r.search(name) for r in rx)
