"""What ``jax.profiler.ProfileData`` does not expose of a trace: the op
metadata of device events, and the host events that pair each device
program run with its enqueue and completion.

``reduce(path)`` reads one ``.xplane.pb`` as the ``XSpace`` protobuf
(message and field numbers of ``tsl/profiler/protobuf/xplane.proto``,
declared here for the installed ``google.protobuf``) and returns an
``XTrace``:

- **Clock alignment.**  Each device ``XLA Modules`` event carries the
  ``run_id`` of its host ``DoEnqueueProgram`` and ``CompleteCallbacks``
  events (the ``_c``/``_p`` flow ids link them too).  A program starts on
  the device after the host enqueued it and ends before the host's
  completion callbacks start, so the device clock's offset from the
  host's, ``delta = device - host``, lies between
  ``max(module_end - callbacks_start)`` and
  ``min(module_start - enqueue_start)``.  Device events are shifted by
  minus the upper bound: after that no module starts before its enqueue.
  On the chip the lower bound can lie above the upper (a module's end
  stamped after its host callbacks began); the report says by how much.
- **Scopes.**  Each device op's ``tf_op`` stat is its name stack
  (``jit(round)/while/body/transpose(jvp(fl.local.loss))/mul:``).  Its
  scope is the innermost component naming an ``fl.`` scope, as written:
  ``jvp(fl.local.loss)`` (forward), ``transpose(jvp(fl.local.loss))``
  (backward), ``fl.local.update``, ``fl.encode``, ``fl.reduce``,
  ``fl.server_update``; ops under none are ``UNSCOPED``.
- **Program spans.**  The host spans whose names start with ``fl.``, on the
  thread that wrote ``bench.window`` and inside it (every thread, where
  the trace has no window).  They nest by construction, unlike the
  benchmark's own spans.

Device seconds are averaged over the chips, control flow left out, as in
``bench.trace``; busy time and idle gaps are on the aligned clock, inside
the window.  Times are seconds.
"""
from __future__ import annotations

import functools
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import trace as tr

UNSCOPED = "(unscoped)"
NO_SPAN = "(no fl span)"
SPAN_PREFIX = "fl."
ENQUEUE, CALLBACKS, MODULES, OPS = ("DoEnqueueProgram", "CompleteCallbacks",
                                    "XLA Modules", "XLA Ops")
_SCOPE = re.compile(r"(?:^|[(/])fl\.")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@functools.lru_cache(maxsize=1)
def _messages():
    """The XSpace message classes, from the field numbers of xplane.proto
    (only the fields read here; the others parse as unknown fields)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(parent, name, fields, oneof=None):
        m = parent.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for number, fname, ftype, *rest in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=F.LABEL_REPEATED if "repeated" in rest else F.LABEL_OPTIONAL)
            if ftype == F.TYPE_MESSAGE:
                f.type_name = rest[0]
            if oneof and "oneof" in rest:
                f.oneof_index = 0
        return m

    def map_entry(parent_msg, name, value_type):
        e = parent_msg.nested_type.add(name=name)
        e.options.map_entry = True
        e.field.add(name="key", number=1, type=F.TYPE_INT64, label=F.LABEL_OPTIONAL)
        e.field.add(name="value", number=2, type=F.TYPE_MESSAGE,
                    label=F.LABEL_OPTIONAL, type_name=value_type)

    T = fdp.message_type
    message(T, "XSpace", [(1, "planes", F.TYPE_MESSAGE, ".bench_xplane.XPlane", "repeated")])
    plane = message(T, "XPlane", [
        (1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING),
        (3, "lines", F.TYPE_MESSAGE, ".bench_xplane.XLine", "repeated"),
        (4, "event_metadata", F.TYPE_MESSAGE,
         ".bench_xplane.XPlane.EventMetadataEntry", "repeated"),
        (5, "stat_metadata", F.TYPE_MESSAGE,
         ".bench_xplane.XPlane.StatMetadataEntry", "repeated"),
    ])
    map_entry(plane, "EventMetadataEntry", ".bench_xplane.XEventMetadata")
    map_entry(plane, "StatMetadataEntry", ".bench_xplane.XStatMetadata")
    message(T, "XLine", [
        (1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING),
        (3, "timestamp_ns", F.TYPE_INT64),
        (4, "events", F.TYPE_MESSAGE, ".bench_xplane.XEvent", "repeated"),
    ])
    message(T, "XEvent", [
        (1, "metadata_id", F.TYPE_INT64), (2, "offset_ps", F.TYPE_INT64),
        (3, "duration_ps", F.TYPE_INT64),
        (4, "stats", F.TYPE_MESSAGE, ".bench_xplane.XStat", "repeated"),
    ])
    message(T, "XStat", [
        (1, "metadata_id", F.TYPE_INT64), (2, "double_value", F.TYPE_DOUBLE, "oneof"),
        (3, "uint64_value", F.TYPE_UINT64, "oneof"), (4, "int64_value", F.TYPE_INT64, "oneof"),
        (5, "str_value", F.TYPE_STRING, "oneof"), (6, "bytes_value", F.TYPE_BYTES, "oneof"),
        (7, "ref_value", F.TYPE_UINT64, "oneof"),
    ], oneof="value")
    message(T, "XEventMetadata", [
        (1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING),
        (4, "display_name", F.TYPE_STRING),
        (5, "stats", F.TYPE_MESSAGE, ".bench_xplane.XStat", "repeated"),
    ])
    message(T, "XStatMetadata", [(1, "id", F.TYPE_INT64), (2, "name", F.TYPE_STRING)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_space(path: str | Path):
    space = _messages()()
    space.ParseFromString(Path(path).read_bytes())
    return space


def stats(plane, stat_list) -> dict:
    """An event's or a metadata's stats by name; a reference stat reads as
    the name it refers to."""
    out = {}
    for s in stat_list:
        kind = s.WhichOneof("value")
        if kind is None:
            continue
        value = getattr(s, kind)
        if kind == "ref_value":
            value = plane.stat_metadata[value].name
        out[plane.stat_metadata[s.metadata_id].name] = value
    return out


def scope_of(tf_op: str) -> str:
    """The innermost name-stack component that names an ``fl.`` scope, as
    written (``transpose(jvp(fl.local.loss))``), else ``UNSCOPED``.  The
    ``:<op type>`` tail of ``tf_op`` is dropped."""
    for part in reversed(tf_op.rsplit(":", 1)[0].split("/")):
        if _SCOPE.search(part):
            return part
    return UNSCOPED


@dataclass
class Clock:
    """The device clock's offset from the host's, from ``pairs`` runs."""

    pairs: int = 0
    upper: float | None = None      # min(module_start - enqueue_start)
    lower: float | None = None      # max(module_end - callbacks_start)

    @property
    def shift(self) -> float:
        """Added to device times: minus the upper bound (0 unpaired)."""
        return 0.0 if self.upper is None else -self.upper


@dataclass
class Ops:
    """One device's XLA ops, on the aligned clock, clipped to the window."""

    starts: np.ndarray
    ends: np.ndarray
    labels: np.ndarray          # index into XTrace.labels
    scopes: np.ndarray          # index into XTrace.scopes
    control: np.ndarray         # bool: control flow (its event covers its body)


@dataclass
class XTrace:
    window: tuple[float, float] | None
    clock: Clock
    labels: list[str]
    scopes: list[str]
    devices: dict[str, Ops] = field(default_factory=dict)
    spans: list[tuple[float, float, str]] = field(default_factory=list)
    modules: list[tuple[float, float]] = field(default_factory=list)   # aligned
    enqueues: list[float] = field(default_factory=list)                # paired

    # ------------------------------------------------------------ device
    def _sum_by(self, key, size: int) -> np.ndarray:
        """Device seconds by ``key(ops)``, control flow left out, mean over chips."""
        out = np.zeros(size)
        for d in self.devices.values():
            keep = ~d.control
            out += np.bincount(key(d)[keep], weights=(d.ends - d.starts)[keep],
                               minlength=size)
        return out / max(1, len(self.devices))

    def scope_seconds(self) -> dict[str, float]:
        """Device seconds by scope."""
        out = self._sum_by(lambda d: d.scopes, len(self.scopes))
        return {n: float(v) for n, v in zip(self.scopes, out) if v > 0}

    def scope_label_seconds(self) -> dict[tuple[str, str], float]:
        """Device seconds by (scope, op label)."""
        n = len(self.labels)
        out = self._sum_by(lambda d: d.scopes * n + d.labels, len(self.scopes) * n)
        return {(self.scopes[k // n], self.labels[k % n]): float(out[k])
                for k in np.flatnonzero(out > 0)}

    def seconds_where(self, pick) -> float | None:
        """Device seconds of the scopes ``pick(scope)`` accepts; None where
        the trace has none of them."""
        hits = [v for s, v in self.scope_seconds().items() if s != UNSCOPED and pick(s)]
        return sum(hits) if hits else None

    def busy_s(self) -> float:
        """The busy union of every op (control flow included), mean over chips."""
        if not self.devices:
            return 0.0
        total = 0.0
        for d in self.devices.values():
            a, b = tr._union(d.starts, d.ends)
            total += float(np.sum(b - a))
        return total / len(self.devices)

    # -------------------------------------------------------------- host
    def span_seconds(self) -> dict[str, list[float]]:
        """Durations of the program's spans, by name."""
        out: dict[str, list[float]] = {}
        for s, e, n in self.spans:
            out.setdefault(n, []).append(e - s)
        return out

    def idle_by_span(self) -> dict[str, float]:
        """Idle device seconds (mean over chips) by the innermost program
        span open at each instant of idle time, on the aligned clock."""
        if self.window is None:
            return {}
        lo, hi = self.window
        times, names = tr._flatten(self.spans)
        edges = np.concatenate([[lo], np.clip(times, lo, hi), [hi]])
        owners = [NO_SPAN] + [n or NO_SPAN for n in names]   # of [edges[k], edges[k+1])
        ids = {n: i for i, n in enumerate(dict.fromkeys(owners))}
        total = np.zeros(len(ids))
        for d in self.devices.values():
            a, b = tr._union(d.starts, d.ends)
            idle = np.diff(edges) - np.diff(_busy_before(edges, a, b))
            total += np.bincount([ids[n] for n in owners], weights=idle, minlength=len(ids))
        total /= max(1, len(self.devices))
        return {n: float(total[i]) for n, i in ids.items() if total[i] > 0}

    # ------------------------------------------------------------ report
    def line(self, rounds: int, top: int = 3) -> str:
        """One line for standard error: the clock offset and its bounds,
        device ms a round by scope with each scope's largest op labels,
        the unscoped share, and idle ms a round by program span."""
        r = max(1, rounds)
        ms = lambda s: f"{1e3 * s / r:.3f}"  # noqa: E731
        c = self.clock
        parts = ["[xspace] clock offset not paired (0 ms)"]
        if c.pairs:
            cross = (f"; the bounds cross by {1e3 * (c.lower - c.upper):.4f} ms"
                     if c.lower > c.upper else "")
            parts = [f"[xspace] clock offset {1e3 * c.upper:.4f} ms (upper bound "
                     f"{1e3 * c.upper:.4f}, lower bound {1e3 * c.lower:.4f} ms, "
                     f"{c.pairs} runs{cross})"]
        by_scope = self.scope_seconds()
        ops = sum(by_scope.values())
        pairs = self.scope_label_seconds()
        scoped = []
        for s, v in sorted(by_scope.items(), key=lambda kv: -kv[1]):
            tops = sorted(((lab, t) for (sc, lab), t in pairs.items() if sc == s),
                          key=lambda kv: -kv[1])[:top]
            scoped.append(f"{s} {ms(v)} [" + ", ".join(f"{lab} {ms(t)}" for lab, t in tops)
                          + "]")
        share = 100.0 * by_scope.get(UNSCOPED, 0.0) / ops if ops > 0 else 0.0
        parts.append(f"device ms a round over {rounds} rounds: ops {ms(ops)}, busy "
                     f"{ms(self.busy_s())}; by scope: " + "; ".join(scoped))
        parts.append(f"unscoped share {share:.3f}%")
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])
        parts.append("idle ms a round by program span: "
                     + ", ".join(f"{n} {ms(v)}" for n, v in idle))
        return " | ".join(parts)


def _busy_before(t: np.ndarray, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Busy seconds before each time ``t`` of the disjoint sorted intervals
    ``(a, b)``."""
    if len(a) == 0:
        return np.zeros(len(t))
    done = np.concatenate([[0.0], np.cumsum(b - a)])
    i = np.searchsorted(a, t, side="right")     # intervals begun by t
    last = np.maximum(i - 1, 0)
    part = np.where(i > 0, np.minimum(t, b[last]) - a[last], 0.0)
    return done[last] + part


def reduce(path: str | Path) -> XTrace:
    """The trace at ``path`` as an ``XTrace``."""
    space = read_space(path)
    enq, cb, window = {}, {}, None
    span_lines: list[list] = []     # the fl spans of each host thread
    window_spans: list = []         # those of the thread that wrote the window
    raw_modules, raw_ops = {}, {}
    labels: dict[str, int] = {}
    scopes: dict[str, int] = {UNSCOPED: 0}
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}
        if plane.name.startswith("/host:"):
            pair_ids = {k for k, n in names.items() if n in (ENQUEUE, CALLBACKS)}
            span_ids = {k for k, n in names.items() if n.startswith(SPAN_PREFIX)}
            window_ids = {k for k, n in names.items() if n == tr.WINDOW_SPAN}
            for line in plane.lines:
                t0, spans = line.timestamp_ns, []
                span_lines.append(spans)
                for e in line.events:
                    mid = e.metadata_id
                    if mid in span_ids or mid in window_ids:
                        s = (t0 + e.offset_ps * 1e-3) * 1e-9
                        span = (s, s + e.duration_ps * 1e-12, names[mid])
                        if mid in window_ids:
                            window, window_spans = span[:2], spans
                        if mid in span_ids:
                            spans.append(span)
                    elif mid in pair_ids:
                        st = stats(plane, e.stats)
                        key = (int(st.get("device_ordinal", 0)), int(st.get("run_id", -1)))
                        (enq if names[mid] == ENQUEUE else cb)[key] = \
                            (t0 + e.offset_ps * 1e-3) * 1e-9
            continue
        m = _DEVICE.match(plane.name)
        if not m:
            continue
        ordinal = int(m.group(1))
        meta = {}     # metadata id -> (label id, scope id, control flow)
        for k, md in plane.event_metadata.items():
            lab = tr.op_label(md.name)
            scope = scope_of(str(stats(plane, md.stats).get("tf_op", "")))
            meta[k] = (labels.setdefault(lab, len(labels)),
                       scopes.setdefault(scope, len(scopes)), lab in tr.CONTROL_FLOW)
        for line in plane.lines:
            t0 = line.timestamp_ns * 1e-9
            if line.name == MODULES:
                for e in line.events:
                    run = int(stats(plane, e.stats).get("run_id", -1))
                    s = t0 + e.offset_ps * 1e-12
                    raw_modules[(ordinal, run)] = (s, s + e.duration_ps * 1e-12)
            elif line.name == OPS:
                rows = [(t0 + e.offset_ps * 1e-12, e.duration_ps * 1e-12, *meta[e.metadata_id])
                        for e in line.events]
                raw_ops[plane.name] = rows

    clock = Clock()
    paired = [k for k in raw_modules if k in enq and k in cb]
    if paired:
        clock = Clock(pairs=len(paired),
                      upper=min(raw_modules[k][0] - enq[k] for k in paired),
                      lower=max(raw_modules[k][1] - cb[k] for k in paired))
    shift = clock.shift
    if window is not None:
        spans = [s for s in window_spans if s[0] >= window[0] and s[1] <= window[1]]
    else:
        spans = [s for line in span_lines for s in line]
    lo, hi = window if window else (-np.inf, np.inf)
    devices = {}
    for name, rows in raw_ops.items():
        a = np.asarray(rows, np.float64).reshape(-1, 5)
        s, e = a[:, 0] + shift, a[:, 0] + a[:, 1] + shift
        keep = (e > lo) & (s < hi)
        devices[name] = Ops(np.maximum(s[keep], lo), np.minimum(e[keep], hi),
                            a[keep, 2].astype(np.int64), a[keep, 3].astype(np.int64),
                            a[keep, 4].astype(bool))
    return XTrace(
        window=window, clock=clock,
        labels=sorted(labels, key=labels.get), scopes=sorted(scopes, key=scopes.get),
        devices=devices, spans=sorted(spans),
        modules=[(raw_modules[k][0] + shift, raw_modules[k][1] + shift) for k in sorted(paired)],
        enqueues=[enq[k] for k in sorted(paired)])


@functools.lru_cache(maxsize=1)
def _reduce_once(path: str, mtime_ns: int, rounds: int) -> XTrace:
    x = reduce(path)
    print(x.line(rounds), file=sys.stderr, flush=True)
    return x


def of(ctx) -> XTrace | None:
    """The reduction of a traced run's trace, made once per run; making it
    prints its line on standard error.  None for an untraced run."""
    if ctx.trace is None:
        return None
    from bench import harness

    path = tr.find_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    return _reduce_once(str(path), path.stat().st_mtime_ns, ctx.window.rounds)


def per_round_ms(ctx, pick) -> float | None:
    """Device ms a round of the scopes ``pick(scope)`` accepts."""
    x = of(ctx)
    if x is None or ctx.window.rounds <= 0:
        return None
    s = x.seconds_where(pick)
    return None if s is None else 1e3 * s / ctx.window.rounds


def mean_span_ms(ctx, name: str) -> float | None:
    """Mean duration of the program span ``name`` in the window, ms."""
    x = of(ctx)
    spans = x.span_seconds().get(name) if x is not None else None
    return 1e3 * sum(spans) / len(spans) if spans else None
