"""Reduction of one profiler trace (an ``.xplane.pb``) to the numbers the
per-layer metric readers take.

What is read, and from where:

* device ops -- the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.  Each
  op's metadata gives its ``tf_op`` (the op's name scope path, which carries
  the program's ``obs:...`` scopes), its ``hlo_category`` and its program.
* device programs -- the ``XLA Modules`` line of the same planes.
* host spans -- events named ``bench:...`` on any thread of ``/host:CPU``:
  the ``jax.profiler.TraceAnnotation`` spans the harness puts around its own
  calls into each layer.

Times are nanoseconds on the host's clock.  A device's clock can run a
millisecond or so ahead of the host's in one trace (its first op then
"starts" before the host span that dispatched it): each device's times are
shifted by the least amount that puts its first op after the first host
span inside the window.  An idle gap is attributed to a host span no more
finely than that.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

from bench.metrics import xplane_pb2

#: ``hlo_category`` values of ops that hold other ops (a loop's ``while``
#: spans its body's ops, which the line lists too): left out of every sum
CONTAINERS = ("while", "conditional", "call")

#: ``hlo_category`` values of the ops that move data between chips
COLLECTIVE_CATEGORIES = ("collective-permute", "all-reduce", "all-gather",
                         "reduce-scatter", "all-to-all", "send", "recv")


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def _stat_value(s):
    for f in ("str_value", "int64_value", "uint64_value", "double_value",
              "ref_value"):
        if s.HasField(f):
            return getattr(s, f)
    return None


class Trace:
    """The device ops, device programs and host spans of one trace."""

    def __init__(self, path: str):
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        self.devices: dict[int, dict] = {}
        spans = []
        for plane in xs.planes:
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            if m:
                self.devices[int(m.group(1))] = self._device(plane)
            elif plane.name == "/host:CPU":
                spans.extend(self._host_spans(plane))
        spans.sort(key=lambda s: s[1])
        self.spans = spans                      # [(name, start, end)]
        inner = [s[1] for s in spans if s[0] != "bench:window"]
        for dev in self.devices.values():
            first = [dev[k]["start"][0] for k in ("ops", "modules")
                     if len(dev[k]["start"])]
            if inner and first:
                shift = max(0.0, inner[0] - min(first))
                for k in ("ops", "modules"):
                    dev[k]["start"] = dev[k]["start"] + shift
                    dev[k]["end"] = dev[k]["end"] + shift

    @staticmethod
    def _device(plane) -> dict:
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for k, md in plane.event_metadata.items():
            st = {stat_name.get(s.metadata_id): _stat_value(s)
                  for s in md.stats}
            meta[k] = (md.display_name or md.name, st.get("tf_op") or "",
                       st.get("hlo_category") or "")
        out = {}
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            base = line.timestamp_ns
            n = len(line.events)
            start = np.empty(n, np.float64)
            dur = np.empty(n, np.float64)
            ids = []
            for i, ev in enumerate(line.events):
                start[i] = base + ev.offset_ps / 1e3
                dur[i] = ev.duration_ps / 1e3
                ids.append(ev.metadata_id)
            names = [meta.get(i, ("?", "", "")) for i in ids]
            out[line.name] = dict(
                start=start, end=start + dur, dur=dur,
                name=[x[0] for x in names], tf_op=[x[1] for x in names],
                category=[x[2] for x in names])
        empty = dict(start=np.zeros(0), end=np.zeros(0), dur=np.zeros(0),
                     name=[], tf_op=[], category=[])
        return {"ops": out.get("XLA Ops", dict(empty)),
                "modules": out.get("XLA Modules", dict(empty))}

    @staticmethod
    def _host_spans(plane):
        names = {k: md.name for k, md in plane.event_metadata.items()}
        out = []
        for line in plane.lines:
            base = line.timestamp_ns
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                if name.startswith("bench:"):
                    s = base + ev.offset_ps / 1e3
                    out.append((name, s, s + ev.duration_ps / 1e3))
        return out

    # -- the window ----------------------------------------------------------

    def window(self, name: str = "bench:window") -> tuple[float, float]:
        for n, s, e in self.spans:
            if n == name:
                return s, e
        raise ValueError(f"no host span {name!r} in the trace")

    # -- device ops ----------------------------------------------------------

    def ops(self, dev: int, window=None) -> dict:
        """The device's ops that start inside ``window``."""
        o = self.devices[dev]["ops"]
        if window is None:
            return o
        keep = np.nonzero((o["start"] >= window[0]) & (o["start"] < window[1]))[0]
        return dict(start=o["start"][keep], end=o["end"][keep],
                    dur=o["dur"][keep],
                    name=[o["name"][i] for i in keep],
                    tf_op=[o["tf_op"][i] for i in keep],
                    category=[o["category"][i] for i in keep])

    def modules(self, dev: int, window=None) -> dict:
        o = self.devices[dev]["modules"]
        keep = np.arange(len(o["start"])) if window is None else np.nonzero(
            (o["start"] >= window[0]) & (o["start"] < window[1]))[0]
        return dict(start=o["start"][keep], end=o["end"][keep],
                    dur=o["dur"][keep], name=[o["name"][i] for i in keep])


# -- reductions ---------------------------------------------------------------

def _is_container(ops: dict) -> np.ndarray:
    return np.asarray([c in CONTAINERS for c in ops["category"]], bool)


def category_mask(ops: dict, categories) -> np.ndarray:
    return np.asarray([any(c in cat for c in categories)
                       for cat in ops["category"]], bool)


def busy_intervals(start, end):
    """Union of [start, end) intervals, as sorted disjoint arrays."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start)
    s, e = np.asarray(start)[order], np.asarray(end)[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.nonzero(new)[0]
    ends = np.append(run_end[idx[1:] - 1], run_end[-1])
    return s[idx], ends


def _work(o: dict) -> tuple[np.ndarray, np.ndarray]:
    """Start and end times of the ops that do work, containers left out: a
    loop's ``while`` spans its whole body, idle time inside it included."""
    keep = ~_is_container(o)
    return o["start"][keep], o["end"][keep]


def busy_ns(trace: Trace, dev: int, window) -> float:
    """Union of the device's working ops inside ``window``."""
    start, end = _work(trace.ops(dev, window))
    s, e = busy_intervals(start, np.minimum(end, window[1]))
    return float(np.sum(e - s))


def scope_ns(trace: Trace, dev: int, window, scope: str) -> float:
    """Device time of the ops whose name scope path holds ``scope``."""
    o = trace.ops(dev, window)
    return float(sum(d for d, t, c in zip(o["dur"], o["tf_op"], o["category"])
                     if scope in t and c not in CONTAINERS))




def exposed_ns(trace: Trace, dev: int, window, categories) -> float:
    """Time of the ops of ``categories`` during which no other op runs on
    the device."""
    o = trace.ops(dev, window)
    is_c = category_mask(o, categories)
    if not is_c.any():
        return 0.0
    rest = ~is_c & ~_is_container(o)
    cs, ce = busy_intervals(o["start"][is_c], o["end"][is_c])
    bs, be = busy_intervals(o["start"][rest], o["end"][rest])
    total = float(np.sum(ce - cs))
    # subtract the overlap of the collective union with the compute union
    i = j = 0
    overlap = 0.0
    while i < len(cs) and j < len(bs):
        lo, hi = max(cs[i], bs[j]), min(ce[i], be[j])
        if hi > lo:
            overlap += hi - lo
        if ce[i] < be[j]:
            i += 1
        else:
            j += 1
    return total - overlap


#: scopes whose name goes one level deeper (``obs:serve/decode``)
_TWO_LEVEL = ("obs:serve", "obs:codec", "obs:kernel", "obs:consensus")


def _scope_label(tf_op: str) -> str:
    """The innermost ``obs:`` scope of an op's name scope path."""
    parts = tf_op.split("/")
    idx = [i for i, p in enumerate(parts) if p.startswith("obs:")]
    if not idx:
        return "(no obs scope)"
    i = idx[-1]
    if parts[i] in _TWO_LEVEL and i + 1 < len(parts):
        return parts[i] + "/" + parts[i + 1]
    return parts[i]


def top_device_ops(trace: Trace, window, n: int = 10):
    """[[label, seconds]] of the costliest (scope, category) pairs, summed
    over the devices and averaged per device."""
    tot: dict[str, float] = {}
    for dev in trace.devices:
        o = trace.ops(dev, window)
        for d, t, c in zip(o["dur"], o["tf_op"], o["category"]):
            if c in CONTAINERS:
                continue
            key = f"{_scope_label(t)} | {c or 'op'}"
            tot[key] = tot.get(key, 0.0) + d
    nd = max(len(trace.devices), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in top]


def idle_gaps(trace: Trace, window, n: int = 10):
    """[[host span, seconds]]: device idle time inside ``window``, summed by
    the innermost ``bench:`` host span open at the middle of each gap,
    averaged over the devices."""
    spans = [s for s in trace.spans if s[0] != "bench:window"]
    starts = np.asarray([s[1] for s in spans])
    tot: dict[str, float] = {}
    for dev in trace.devices:
        s, e = busy_intervals(*_work(trace.ops(dev, window)))
        gap_s = np.concatenate([[window[0]], e])
        gap_e = np.concatenate([s, [window[1]]])
        for a, b in zip(gap_s, gap_e):
            a, b = max(a, window[0]), min(b, window[1])
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            label = "(no host span)"
            # the latest-starting span open at ``mid`` is the innermost one
            i = int(np.searchsorted(starts, mid, side="right")) - 1
            for j in range(i, max(i - 64, -1), -1):
                if spans[j][2] > mid:
                    label = spans[j][0]
                    break
            tot[label] = tot.get(label, 0.0) + (b - a)
    nd = max(len(trace.devices), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in top]
