"""The trace reducer: ``.xplane.pb`` -> device busy / idle, per-executable
and per-kernel device time, idle gaps by what the host was doing.

``extract`` turns the profiler's file into plain lists (what
``tests/data/trace_small.json.gz`` holds, recorded on the chip);
``reduce`` turns those into a ``TraceData``.  Every PR computes the same
numbers in the same way from here.

On a TPU the profiler writes one plane per chip (``/device:TPU:<n>``)
with a line of executables (``XLA Modules``: one event per run of a
jitted program, named ``jit_<function>(<fingerprint>)``) and a line of
the operations inside them (``XLA Ops``), and host planes with one line
per thread.  All share one clock; ``SYNC_SPAN`` is a host span the
benchmark emits at a known ``time.perf_counter()`` to tie that clock to
the load generator's.
"""

import bisect
import glob
import gzip
import json
import os
import re

SYNC_SPAN = "bench_clock_sync"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
TOP = 10
OP_TEXT = 160     # enough of an operation's text for its name and result type


def find(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError("the profiler wrote no .xplane.pb under " + trace_dir)
    return files[-1]


def extract(path):
    """Plain lists from the profiler's file: per device plane its module
    runs and operations, and the host threads' spans.  Times in ns."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    raw = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = raw["devices"].setdefault(m.group(1), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
                if key:
                    dev[key].extend([e.name[:OP_TEXT], e.start_ns, e.duration_ns]
                                    for e in line.events)
        elif plane.name.startswith("/host:") and plane.name != "/host:metadata":
            for line in plane.lines:
                raw["host"].extend([line.name, e.name, e.start_ns, e.duration_ns]
                                   for e in line.events if e.duration_ns > 0)
    return raw


def save(raw, path):
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)


def load_raw(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    total, end = 0.0, None
    for s, d in sorted(intervals):
        if end is None or s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def _module_name(event_name):
    """``jit_paged_admit(123)`` -> ``paged_admit``."""
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def op_base(text):
    """``%decode_attention.20 = bf16[..] custom-call(..)`` ->
    ``decode_attention``: the operation without its instance number."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+|\.remat\d*)+$", "", name)


def op_shape(text):
    """The operation's (first) result type, ``bf16[32,2048,128]``."""
    m = re.search(r" = \(?([a-z]+[0-9]*\[[0-9,]*\])", text)
    return m.group(1) if m else ""


def shape_dims(shape):
    inner = shape[shape.index("[") + 1:-1] if "[" in shape else ""
    return [int(x) for x in inner.split(",") if x]


class Run:
    """One run of one executable on the device, with its operations."""

    __slots__ = ("module", "label", "start", "dur", "ops")

    def __init__(self, module, start, dur):
        self.module, self.label, self.start, self.dur = module, module, start, dur
        self.ops = []       # (base name, result type, seconds)

    def op_seconds(self, pattern):
        return sum(d for n, _, d in self.ops if pattern in n)

    def op_dims(self, pattern):
        """Result dims of the first operation whose name holds ``pattern``."""
        return next((shape_dims(s) for n, s, _ in self.ops if pattern in n), [])


class TraceData:
    """What the readers ask of one traced window (seconds throughout).

    ``labels`` names executables the program left nameless: a list of
    ``(label, "op" | "module", pattern)``; a run takes the first label
    whose pattern its operations' names (or its module's name) hold.
    The program's decode step and its prefills are all ``jit__unknown``
    (``jax.jit`` of a ``functools.partial``), so they are told apart by
    the Pallas kernel inside."""

    def __init__(self, raw, sync_perf_counter=None, labels=()):
        self.runs, self.busy_s, self.window_s = [], 0.0, 0.0
        self._spans = []    # the first chip's busy intervals
        self.t_lo = self.t_hi = None
        busy = []
        for n, (_, dev) in enumerate(sorted(raw["devices"].items())):
            ops = sorted((s * 1e-9, d * 1e-9, text) for text, s, d in dev["ops"])
            mods = sorted((s * 1e-9, d * 1e-9, _module_name(name))
                          for name, s, d in dev["modules"])
            spans = [(s, d) for s, d, _ in (ops or mods)]
            if spans:
                busy.append(_union(spans))
                lo = min(s for s, _ in spans)
                hi = max(s + d for s, d in spans)
                self.t_lo = lo if self.t_lo is None else min(self.t_lo, lo)
                self.t_hi = hi if self.t_hi is None else max(self.t_hi, hi)
            if n:
                continue        # per-executable detail: the first chip's
            self._spans = spans
            runs = [Run(name, s, d) for s, d, name in mods]
            starts = [r.start for r in runs]
            for s, d, text in ops:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < runs[i].start + runs[i].dur:
                    runs[i].ops.append((op_base(text), op_shape(text), d))
            for r in runs:
                for label, what, pattern in labels:
                    if (what == "module" and pattern in r.module) or (
                            what == "op" and any(pattern in n
                                                 for n, _, _ in r.ops)):
                        r.label = label
                        break
            self.runs = runs
        if busy:
            self.busy_s = sum(busy) / len(busy)
            self.window_s = self.t_hi - self.t_lo
        self.host = sorted((s * 1e-9, d * 1e-9, thread, n)
                           for thread, n, s, d in raw["host"])
        # perf_counter - trace clock, where the sync span was found
        self.clock_offset = None
        if sync_perf_counter is not None:
            at = [s for s, _, _, n in self.host if n == SYNC_SPAN]
            if at:
                self.clock_offset = sync_perf_counter - at[0]

    def runs_of(self, label):
        return [r for r in self.runs if r.label == label]

    def interval(self):
        """The traced window on the ``time.perf_counter`` clock."""
        if self.clock_offset is None or self.t_lo is None:
            return None
        return self.t_lo + self.clock_offset, self.t_hi + self.clock_offset

    def idle_gaps(self):
        """``[(start, duration)]`` between busy intervals of the first chip."""
        gaps, end = [], None
        for s, d in self._spans:
            if end is not None and s > end:
                gaps.append((end, s - end))
            end = s + d if end is None else max(end, s + d)
        return gaps

    def host_during(self, start, duration):
        """The host span that covers most of [start, start+duration), the
        innermost where several do: what the host was doing while the
        device idled."""
        best, cover_best, d_best = "nothing traced on the host", 0.0, 0.0
        lo = bisect.bisect_left(self.host, (start - 5.0,))
        for s, d, _, n in self.host[lo:]:
            if s >= start + duration:
                break
            cover = min(s + d, start + duration) - max(s, start)
            if cover <= 0 or n == SYNC_SPAN:
                continue
            if cover > cover_best * 1.001 or (
                    cover >= cover_best * 0.999 and d < d_best):
                best, cover_best, d_best = n, cover, d
        return best


def load(trace_dir, sync_perf_counter=None, labels=()):
    return TraceData(extract(find(trace_dir)), sync_perf_counter, labels)


def _short(name):
    return re.sub(r"[^A-Za-z0-9_.\-/]+", "_", name).strip("_")[:80]


def breakdown(t):
    """The result line's ``breakdown``: the device operations that took
    most time, by executable and result type, and the longest idle gaps
    by what the host was doing."""
    by_op = {}
    for r in t.runs:
        for name, shape, d in r.ops:
            key = _short("{}/{}_{}".format(r.label, name, shape))
            by_op[key] = by_op.get(key, 0.0) + d
    by_gap = {}
    for s, d in sorted(t.idle_gaps(), key=lambda g: -g[1])[:300]:
        key = _short(t.host_during(s, d))
        by_gap[key] = by_gap.get(key, 0.0) + d

    def top(m):
        return [[k, v] for k, v in sorted(m.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"device_ops": top(by_op), "idle_gaps": top(by_gap)}
