"""The program's own spans, counters and stage maps, joined with a
profiler trace of the chip.

With the span recorder on (``repro.common.spans``), a traced run gives,
beside the ``.xplane.pb``:

- the program's spans (``fl.round``, ``fl.feed``, ``fl.dispatch``,
  ``fl.sync``, ...) on the wall clock, as the harness's ``bench.*``
  spans are;
- its counters by round (``host_syncs``);
- for each compiled round program dispatched, the stage of each HLO
  instruction (``CompileGuard.stage_maps``), keyed by HLO module name.

The trace names each ``XLA Ops`` event by its HLO instruction only; the
``XLA Modules`` line of the same device says which program
(``jit_round_step(<fingerprint>)``) ran over that interval.  So an
operation's stage is ``stage_maps[module][instruction]``, and
``unstaged`` where the module or instruction has none.

The traced window is the extent of the harness's spans, as in
``trace.reduce``.  The breakdown names each idle gap by the innermost
span, program or harness, that holds its midpoint; the idle readings
(``idle_pieces``) cut each gap where spans begin and end and give each
piece to the innermost span that holds it, since one gap between two
rounds runs through the end hooks, the harness's wait and the next
feed.

``records_to_json`` puts the program's side in plain JSON, the form of
the recorded fixture; ``ProgramTrace.read`` joins it with the trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from benchmarks.chip import trace
from repro.common.spans import Span

UNSTAGED = "unstaged"
MODULES_LINE = "XLA Modules"


def records_to_json(bench_spans: Sequence[Tuple[int, int, str]],
                    records, stage_maps: Dict[str, Dict[str, Dict[str, str]]]
                    ) -> Dict[str, Any]:
    """One traced run's program side as plain JSON: the harness spans,
    the program's spans and counters (``spans.drain()``) and the stage
    maps of each program (``{program: guard.stage_maps()}``)."""
    return {
        "bench_spans": [[int(s), int(e), n] for s, e, n in bench_spans],
        "spans": [[s.id, s.parent, s.name, s.start_ns, s.end_ns, s.round,
                   s.attrs] for s in records.spans],
        "counters": [[r, n, v] for (r, n), v in records.counters.items()],
        "stage_maps": stage_maps,
    }


def read_xplane(path: str):
    """``(profile start, {device: [(start, end, op)]}, {device: [(start,
    end, module)]})`` of the TPU planes, times in ns from profile start."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    start, ops, mods = None, {}, {}
    for plane in pd.planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                start = int(v)
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        d = int(m.group(1))
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                ops[d] = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            elif line.name == MODULES_LINE:
                mods[d] = [(e.start_ns, e.end_ns, e.name.split("(")[0])
                           for e in line.events]
    if start is None:
        raise ValueError(f"{path}: no profile start time")
    return start, ops, mods


def instruction(op: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``."""
    return op.partition(" = ")[0].lstrip("%").strip()


@dataclasses.dataclass
class ProgramTrace:
    red: trace.Reduced           # window, ops; spans: harness + program
    stages: List[List[str]]      # per device, the stage of each op
    modules: List[List[Tuple[int, int, str]]]   # per device, unclipped
    spans: List[Span]            # the program's, on the trace's time base
    counters: Dict[Tuple[Optional[int], str], int]
    programs: Dict[str, List[str]]   # program -> its HLO module names

    @classmethod
    def read(cls, path: str, program: Dict[str, Any],
             n_devices: int = 0) -> "ProgramTrace":
        """``path`` (``.xplane.pb`` or ``.gz``) with the program side
        ``program`` (``records_to_json``)."""
        red = trace.reduce(path, [tuple(s) for s in program["bench_spans"]],
                           n_devices=n_devices)
        start, _, mods = read_xplane(path)
        # the program's spans on the trace's time base
        prog = [Span(i, p, n, int(s) - start, int(e) - start, r, a)
                for i, p, n, s, e, r, a in program["spans"]]
        modules = [mods[i] for i in sorted(mods)[: n_devices or None]]
        counters = {(r, n): v for r, n, v in program["counters"]}
        return cls.build(red, modules, prog, counters, program["stage_maps"])

    @classmethod
    def build(cls, red: trace.Reduced,
              modules: Sequence[Sequence[Tuple[int, int, str]]],
              prog: List[Span],
              counters: Dict[Tuple[Optional[int], str], int],
              stage_maps: Dict[str, Dict[str, Dict[str, str]]]
              ) -> "ProgramTrace":
        """From the reduced trace (harness spans only), each device's
        module runs, the program's spans on the trace's time base (ns
        from profile start), its counters and its stage maps."""
        by_module = {m: st for per in stage_maps.values()
                     for m, st in per.items()}
        modules = [sorted(m) for m in modules]
        stages = []
        for d, mlist in zip(red.devices, modules):
            starts = [s for s, _, _ in mlist]
            out = []
            for s, op in zip(d.start.tolist(), d.names):
                k = bisect.bisect_right(starts, s) - 1
                mod = mlist[k][2] if k >= 0 and s < mlist[k][1] else None
                out.append(by_module.get(mod, {}).get(instruction(op),
                                                      UNSTAGED))
            stages.append(out)
        named = trace.Reduced(red.t0, red.t1, red.devices,
                              list(red.spans) + [(p.start_ns, p.end_ns,
                                                  p.name) for p in prog])
        return cls(named, stages, modules, prog, counters,
                   {g: sorted(per) for g, per in stage_maps.items()})

    # -- the traced rounds ------------------------------------------------

    def rounds(self) -> List[int]:
        """The rounds whose ``fl.round`` span lies in the window."""
        return sorted(p.round for p in self.spans if p.name == "fl.round"
                      and p.start_ns >= self.red.t0
                      and p.end_ns <= self.red.t1)

    # -- per-layer readings ---------------------------------------------

    def stage_ms(self, stages: Set[str]) -> Optional[float]:
        """Per traced round, the union of the device intervals of the
        operations of ``stages``: the most of any chip, in ms."""
        n = len(self.rounds())
        if not n or not self.red.devices:
            return None
        per = []
        for d, st in zip(self.red.devices, self.stages):
            keep = np.asarray([s in stages for s in st], bool)
            if not keep.any():
                per.append(0)
                continue
            per.append(sum(e - s for s, e in trace._union(d.start[keep],
                                                          d.end[keep])))
        if not any(per):
            return None
        return max(per) * 1e-6 / n

    def idle_gaps(self) -> List[List[Tuple[int, int]]]:
        """Per device, the window's intervals with no operation."""
        out = []
        for d in self.red.devices:
            busy = trace._union(d.start, d.end)
            edges = [self.red.t0] + [x for iv in busy for x in iv] + \
                [self.red.t1]
            out.append([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                        if b > a])
        return out

    def idle_pieces(self) -> List[List[Tuple[int, int, str]]]:
        """Per device, the window's idle time cut where spans begin and
        end, each piece named by the innermost span (program or harness)
        that holds it."""
        spans = sorted(self.red.spans)
        starts = [s for s, _, _ in spans]
        longest = max((e - s for s, e, _ in spans), default=0)
        out = []
        for gaps in self.idle_gaps():
            pieces = []
            for a, b in gaps:
                lo = bisect.bisect_left(starts, a - longest)
                near = [sp for sp in spans[lo:bisect.bisect_left(starts, b)]
                        if sp[1] > a]
                cuts = sorted({a, b} | {x for s, e, _ in near
                                        for x in (s, e) if a < x < b})
                for x, y in zip(cuts, cuts[1:]):
                    inner = [(e - s, n) for s, e, n in near if s <= x < e]
                    pieces.append((x, y, min(inner)[1] if inner
                                   else "outside bench spans"))
            out.append(pieces)
        return out

    def feed_idle_ms(self) -> Optional[float]:
        """Per traced round, the device idle time that lies in an
        ``fl.feed`` span, in ms (mean over the chips)."""
        n = len(self.rounds())
        if not n or not self.red.devices or not any(
                p.name == "fl.feed" for p in self.spans):
            return None
        per = [sum(b - a for a, b, name in pieces
                   if name.startswith("fl.feed"))
               for pieces in self.idle_pieces()]
        return float(np.mean(per)) * 1e-6 / n

    def host_syncs_per_round(self) -> Optional[float]:
        """The ``host_syncs`` counter over the traced rounds, over them."""
        rounds = self.rounds()
        if not rounds or not any(n == "host_syncs"
                                 for _, n in self.counters):
            return None
        return sum(self.counters.get((r, "host_syncs"), 0)
                   for r in rounds) / len(rounds)

    # -- checks on the reading ------------------------------------------

    def unstaged_share(self) -> Optional[float]:
        """The most, over the chips, of the busy time in which only
        operations with no stage run, as a share of the busy time."""
        out = []
        for d, st in zip(self.red.devices, self.stages):
            busy = self.red.busy_ns(d)
            staged = np.asarray([s != UNSTAGED for s in st], bool)
            covered = sum(e - s for s, e in trace._union(d.start[staged],
                                                         d.end[staged]))
            if busy:
                out.append((busy - covered) / busy)
        return max(out) if out else None

    def stage_totals(self) -> Dict[str, float]:
        """Per traced round, the union of each stage's operations, ms
        (the most of any chip)."""
        names = sorted({s for st in self.stages for s in st})
        return {s: self.stage_ms({s}) or 0.0 for s in names}

    def named_idle_share(self) -> Optional[float]:
        """Of the idle time inside ``bench.fit`` spans, the share that
        lies in one of the program's spans (``fl.*``)."""
        fits = [(s, e) for s, e, n in self.red.spans if n == "bench.fit"]
        inside = named = 0
        for pieces in self.idle_pieces():
            for a, b, name in pieces:
                if any(s <= a < e for s, e in fits):
                    inside += b - a
                    named += (b - a) * name.startswith("fl.")
        return named / inside if inside else None

    def idle_by_span(self) -> Dict[str, float]:
        """Idle ms per traced round by the innermost span that holds it
        (mean over the chips)."""
        n = max(1, len(self.rounds()))
        k = max(1, len(self.red.devices))
        tot: Dict[str, float] = {}
        for pieces in self.idle_pieces():
            for a, b, name in pieces:
                tot[name] = tot.get(name, 0.0) + (b - a) * 1e-6 / k / n
        return dict(sorted(tot.items(), key=lambda kv: -kv[1]))

    def leads(self) -> Dict[str, Optional[List[Tuple[int, int]]]]:
        """Per program, ``(dispatch start, lead)`` in ns for each
        ``fl.dispatch`` span in the window, on each chip: the lead runs
        from the span's start to the first device operation of the
        module run it launched, the k-th dispatch paired with the k-th
        run; None where the counts differ.  A negative lead would mean
        the two clocks disagree."""
        out: Dict[str, Optional[List[Tuple[int, int]]]] = {}
        for prog, mods in self.programs.items():
            disp = sorted(p.start_ns for p in self.spans
                          if p.name == "fl.dispatch"
                          and p.attrs.get("program") == prog)
            pairs: Optional[List[Tuple[int, int]]] = []
            for mlist in self.modules:
                runs = sorted(s for s, _, m in mlist if m in mods)
                if len(runs) != len(disp):
                    pairs = None
                    break
                pairs += [(d, r - d) for r, d in zip(runs, disp)
                          if self.red.t0 <= d < self.red.t1]
            out[prog] = pairs
        return out


# -- the per-layer metrics these readings give --------------------------

def local_train_ms(pt: ProgramTrace) -> Optional[float]:
    return pt.stage_ms({"local_train"})


def codec_ms(pt: ProgramTrace) -> Optional[float]:
    return pt.stage_ms({"codec"})


def aggregate_ms(pt: ProgramTrace) -> Optional[float]:
    return pt.stage_ms({"aggregate", "finalize"})


def feed_idle_ms(pt: ProgramTrace) -> Optional[float]:
    return pt.feed_idle_ms()


def host_syncs_per_round(pt: ProgramTrace) -> Optional[float]:
    return pt.host_syncs_per_round()


METRICS = {f.__name__: f for f in (local_train_ms, codec_ms, aggregate_ms,
                                   feed_idle_ms, host_syncs_per_round)}
