"""The reduction from a profiler trace to numbers.

``summarize(path)`` reads one ``.xplane.pb`` with ``jax.profiler.ProfileData``
and returns a ``Summary`` of the traced window:

- per TPU device (planes ``/device:TPU:<n>``), the events of its
  ``XLA Ops`` line.  Each event is named by its HLO instruction
  (``%fedavg_reduce.185 = f32[1,4096]{...} custom-call(...)``); its label
  is the instruction name without the numeric suffix (``fedavg_reduce``,
  ``fusion``, ``convolution``, ``all-reduce``), which for a Pallas kernel
  is the kernel's own name;
- the host spans of the thread that wrote ``WINDOW_SPAN`` (the harness's
  span around the measured stretch), which also bounds the window.

From those: the busy union and idle share of each device; device seconds
by label (control flow such as ``while`` is left out there, since its
event covers the ops of its body); kernel and collective time; collective
time during which no other op ran (exposed); and idle gaps credited to the
innermost host span that covers each gap's midpoint.  Times are seconds.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.window"
COLLECTIVES = {"all-reduce", "all-reduce-start", "all-reduce-done", "all-gather",
               "all-gather-start", "all-gather-done", "reduce-scatter",
               "collective-permute", "collective-permute-start",
               "collective-permute-done", "all-to-all"}
CONTROL_FLOW = {"while", "conditional", "call"}
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|$)")


def _is_kernel(event_name: str, label: str) -> bool:
    """A Pallas kernel: a TPU custom call, which carries the kernel's name
    (an XLA-made custom call keeps the name ``custom-call``)."""
    return ('custom_call_target="tpu_custom_call"' in event_name
            or (" custom-call(" in event_name and label != "custom-call"))


def op_label(event_name: str) -> str:
    """The HLO instruction's name without its numeric suffix."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


@dataclass
class Device:
    starts: np.ndarray          # seconds, clipped to the window
    ends: np.ndarray
    labels: np.ndarray          # index into Summary.label_names
    kernel: np.ndarray          # bool: a Pallas kernel (tpu_custom_call)


@dataclass
class Summary:
    window: tuple[float, float] | None
    label_names: list[str]
    devices: dict[str, Device]
    host: list[tuple[float, float, str]] = field(default_factory=list)

    # ------------------------------------------------------------- window
    def window_s(self) -> float:
        return 0.0 if self.window is None else self.window[1] - self.window[0]

    def busy_intervals(self, device: str):
        d = self.devices[device]
        return _union(d.starts, d.ends)

    def busy_s(self, device: str) -> float:
        a, b = self.busy_intervals(device)
        return float(np.sum(b - a))

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    # ---------------------------------------------------------------- ops
    def _label_mask(self, d: Device, names) -> np.ndarray:
        ids = [i for i, n in enumerate(self.label_names) if n in names]
        return np.isin(d.labels, ids)

    def op_seconds(self) -> dict[str, float]:
        """Device seconds by label, averaged over the devices; control
        flow left out."""
        out = np.zeros(len(self.label_names))
        for d in self.devices.values():
            keep = ~self._label_mask(d, CONTROL_FLOW)
            out += np.bincount(d.labels[keep], weights=(d.ends - d.starts)[keep],
                               minlength=len(self.label_names))
        out /= max(1, len(self.devices))
        return {n: float(v) for n, v in zip(self.label_names, out) if v > 0}

    def kernels(self) -> dict[str, int]:
        """Events of each Pallas kernel in the window, over all devices."""
        out: dict[str, int] = {}
        for d in self.devices.values():
            for i in d.labels[d.kernel]:
                name = self.label_names[i]
                out[name] = out.get(name, 0) + 1
        return out

    def kernel_events(self, kernel: str) -> int:
        return self.kernels().get(kernel, 0)

    def kernel_seconds(self, kernel: str) -> float:
        """Summed durations of one kernel's events, averaged over devices."""
        total = 0.0
        for d in self.devices.values():
            sel = self._label_mask(d, {kernel}) & d.kernel
            total += float(np.sum((d.ends - d.starts)[sel]))
        return total / max(1, len(self.devices))

    def collective_seconds(self) -> float:
        total = 0.0
        for d in self.devices.values():
            sel = self._label_mask(d, COLLECTIVES)
            total += float(np.sum((d.ends - d.starts)[sel]))
        return total / max(1, len(self.devices))

    def exposed_collective_seconds(self) -> float:
        """Collective time during which no other op ran on that device."""
        total = 0.0
        for d in self.devices.values():
            coll = self._label_mask(d, COLLECTIVES)
            other = ~coll & ~self._label_mask(d, CONTROL_FLOW)
            ca, cb = _union(d.starts[coll], d.ends[coll])
            oa, ob = _union(d.starts[other], d.ends[other])
            total += float(np.sum(cb - ca)) - _overlap(ca, cb, oa, ob)
        return total / max(1, len(self.devices))

    # --------------------------------------------------------------- idle
    def idle_gaps(self, device: str):
        """(starts, ends) of the device's idle stretches in the window."""
        a, b = self.busy_intervals(device)
        lo, hi = self.window
        starts = np.concatenate([[lo], b])
        ends = np.concatenate([a, [hi]])
        keep = ends > starts
        return starts[keep], ends[keep]

    def idle_by_host(self) -> dict[str, float]:
        """Idle device seconds (averaged over devices) by the innermost host
        span covering each gap's midpoint."""
        times, names = _flatten(self.host)
        labels = sorted({n for n in names if n is not None}) + ["(no host span)"]
        pos = {n: i for i, n in enumerate(labels)}
        none = len(labels) - 1
        # one more entry for index -1: a gap before the first span
        ids = np.asarray([pos[n] if n is not None else none for n in names] + [none],
                         np.int64)
        total = np.zeros(len(labels))
        for dev in self.devices:
            a, b = self.idle_gaps(dev)
            at = np.searchsorted(times, (a + b) / 2, side="right") - 1
            total += np.bincount(ids[at], weights=b - a, minlength=len(labels))
        total /= max(1, len(self.devices))
        return {n: float(v) for n, v in zip(labels, total) if v > 0}

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals (starts, ends) of possibly overlapping ones."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(s) - 1]])
    return s[new], e[last]


def _overlap(a, b, oa, ob) -> float:
    """Total overlap of the disjoint sorted intervals (a, b) with the
    disjoint sorted intervals (oa, ob)."""
    if len(oa) == 0 or len(a) == 0:
        return 0.0
    cum = np.concatenate([[0.0], np.cumsum(ob - oa)])

    def covered(t):   # length of (oa, ob) before time t
        i = np.searchsorted(oa, t, side="right")
        partial = np.where(i > 0, np.clip(t - oa[np.maximum(i - 1, 0)], 0.0,
                                          (ob - oa)[np.maximum(i - 1, 0)]), 0.0)
        return cum[np.maximum(i - 1, 0)] * (i > 0) + partial

    return float(np.sum(covered(b) - covered(a)))


def _flatten(spans):
    """Properly nested host spans -> (boundary times, the innermost span's
    name from each boundary on; None where no span is open)."""
    times, names, stack = [], [], []
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, _ = stack.pop()
            times.append(end)
            names.append(stack[-1][1] if stack else None)
        stack.append((e, n))
        times.append(s)
        names.append(n)
    while stack:
        end, _ = stack.pop()
        times.append(end)
        names.append(stack[-1][1] if stack else None)
    return np.asarray(times, np.float64), names


def summarize(path: str | Path) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    label_ids: dict[str, int] = {}
    seen: dict[str, tuple[int, bool]] = {}    # event name -> (label id, kernel)
    raw: dict[str, tuple] = {}
    host, window = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            starts, durs, labels, kernel = [], [], [], []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name = e.name
                    hit = seen.get(name)
                    if hit is None:
                        lab = op_label(name)
                        hit = (label_ids.setdefault(lab, len(label_ids)),
                               _is_kernel(name, lab))
                        seen[name] = hit
                    starts.append(e.start_ns)
                    durs.append(e.duration_ns)
                    labels.append(hit[0])
                    kernel.append(hit[1])
            raw[plane.name] = (starts, durs, labels, kernel)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                         for e in line.events]
                for s in spans:
                    if s[2] == WINDOW_SPAN:
                        window = (s[0], s[1])
                        host = spans
    lo, hi = window if window else (-np.inf, np.inf)
    devices = {}
    for name, (starts, durs, labels, kernel) in raw.items():
        s = np.asarray(starts, np.float64) * 1e-9
        e = s + np.asarray(durs, np.float64) * 1e-9
        keep = (e > lo) & (s < hi)
        devices[name] = Device(np.maximum(s[keep], lo), np.minimum(e[keep], hi),
                               np.asarray(labels, np.int64)[keep],
                               np.asarray(kernel, bool)[keep])
    names = [None] * len(label_ids)
    for lab, i in label_ids.items():
        names[i] = lab
    return Summary(window=window, label_names=names, devices=devices, host=host)


def find_xplane(trace_dir: str | Path) -> Path | None:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    return files[-1] if files else None
