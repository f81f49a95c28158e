"""Per-request resource cost ledger + the /debug/top sliding-window profiler.

The observability gap after PR 4: device time, transfer bytes, and
traversed edges existed only as GLOBAL counters (utils/metrics.py) and
per-span annotations (obs/otrace.py, sampled). Neither answers "what did
THIS query cost" or "which plan shape is burning the device" — the
questions the SF100 scale gate and multi-tenant QoS both need.

Model (Dapper-style, like otrace): a request entry point (Node.query,
ClusterClient.query, worker serve_task) mints a CostLedger and installs
it on a contextvar; every execution seam below — Executor._traced_dispatch
(per-task attribution), the device-kernel sites in query/task.py,
DeviceBatcher (batched kernel cost apportioned to members by slot size),
MeshExecutor fused programs, ResidencyManager uploads, DispatchGate waits
and sheds — charges the current ledger. Workers ship their ledger BACK to
the querying node in gRPC trailing metadata (WIRE_KEY, next to the span
payload), so the root assembles ONE cluster-wide cost record with
per-group sub-records; there is no out-of-band collector.

The unarmed fast path is one contextvar read returning None: a node
started with --no_cost_ledger must measure nothing. What the armed ledger
costs a request has not been measured on the chip; tests/test_costs.py
holds what it books.

The stage clock (StageClock, below the ledger) answers the question the
ledger cannot: WHERE inside the request the wall time went. The entry
point that owns a request opens it; `with costs.stage("parse"):` switches
the request's one current stage; `costs.kernel(...)` windows are stages
too (dev.window, or dev.dispatch / dev.wait / dev.post where the site
splits its window), and so are the two waits of a request that shares
the device: `gate.wait` (queued for a slot of the dispatch gate) and
`batch.wait` (a batch leader's wait for companions, a follower's wait
for its leader's launch). It is read three ways off one clock: always-on
/metrics counters (dgraph_stage_us_total{stage=} for the wall time,
dgraph_stage_cpu_us_total{stage=} for the opening thread's CPU time in
it), and for a sampled request child spans in /debug/traces and
jax.profiler annotations. An HTTP request's clock reaches back to the
accept (`http.accept`, `http.head`: StageClock's `before`), and a full
collection of the interpreter's garbage is a stage of the request that
triggered it (`gc`: GcPauses, below the clock).

Completed records land in a CostBook: a bounded sliding window that
powers GET /debug/top (rank plan shapes / predicates / endpoints by
device ms, bytes, edges over the trailing window) and keeps a per-shape
EWMA baseline of device cost — a record whose device_ms exceeds
k x baseline is flagged as a cost regression into the slow-query ring
even when the query finishes under --slow_query_ms.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import json
import threading
import time
from collections import deque

from . import devprof, otrace

# gRPC trailing-metadata key for the shipped record (-bin carries bytes)
WIRE_KEY = "dgt-cost-bin"

_current: contextvars.ContextVar["CostLedger | None"] = \
    contextvars.ContextVar("dgt_cost_ledger", default=None)


def current() -> "CostLedger | None":
    """The active ledger on this execution context, or None (unarmed)."""
    return _current.get()


class scope:
    """Install a ledger (or None) for the dynamic extent of a request.
    Re-entrant and thread-correct: the contextvar token restores whatever
    the enclosing frame had, so a batch leader can suppress gate-level
    attribution with scope(None) while apportioning manually."""

    __slots__ = ("_lg", "_token")

    def __init__(self, lg: "CostLedger | None") -> None:
        self._lg = lg

    def __enter__(self):
        self._token = _current.set(self._lg)
        return self._lg

    def __exit__(self, *a):
        _current.reset(self._token)
        return False


class _TaskScope:
    """Attributes nested kernel charges to one predicate (a stack: the
    fused ANN pipeline dispatches a filter task inside a root task)."""

    __slots__ = ("_lg", "_attr")

    def __init__(self, lg: "CostLedger", attr: str) -> None:
        self._lg = lg
        self._attr = attr

    def __enter__(self):
        self._lg._push_attr(self._attr)
        return self

    def __exit__(self, *a):
        self._lg._pop_attr()
        return False


class CostLedger:
    """One request's resource cost accumulator.

    All mutators take the ledger's own lock: hedged RPCs and batch
    leaders charge a ledger from threads other than the request's own
    (contextvars are copied into the hedge pool; batch runners hold
    explicit references captured at submit time)."""

    __slots__ = ("_lock", "endpoint", "shape", "tenant", "t0", "wall_ms",
                 "device_ms", "h2d_bytes", "d2h_bytes", "upload_bytes",
                 "edges", "rows", "tasks", "gate_wait_ms", "compile_ms",
                 "subs", "outcomes", "per_pred", "kernels", "kernel_calls",
                 "groups", "_attrs", "_kernel_depth")

    def __init__(self, endpoint: str = "", shape: str = "",
                 tenant: str = "") -> None:
        self._lock = threading.Lock()
        self.endpoint = endpoint
        self.shape = shape
        self.tenant = tenant          # requesting namespace ("" = default)
        self.t0 = time.perf_counter()
        self.wall_ms = 0.0
        self.device_ms = 0.0          # device-kernel wall ms (fenced sites)
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.upload_bytes = 0         # residency warm->HBM uploads at serve
        self.edges = 0                # traversed edges
        self.rows = 0                 # value/index rows scanned host-side
        self.tasks = 0                # dispatched tasks
        self.gate_wait_ms = 0.0       # dispatch-gate queueing
        self.compile_ms = 0.0         # XLA compiles this request triggered
        self.subs: tuple = ()         # subscription ids (endpoint="live")
        self.outcomes: dict[str, int] = {}
        # attr -> [device_ms, edges, bytes, tasks]
        self.per_pred: dict[str, list] = {}
        self.kernels: dict[str, float] = {}   # kernel name -> device ms
        self.kernel_calls: dict[str, int] = {}   # kernel name -> windows
        # worker addr -> merged remote record dict (the shipped payload)
        self.groups: dict[str, dict] = {}
        self._attrs: list[str] = []
        self._kernel_depth = 0       # open _KernelTimer windows

    # ---------------------------------------------------------------- scopes

    def task(self, attr: str) -> _TaskScope:
        return _TaskScope(self, attr)

    def _push_attr(self, attr: str) -> None:
        with self._lock:
            self._attrs.append(attr)

    def _pop_attr(self) -> None:
        with self._lock:
            if self._attrs:
                self._attrs.pop()

    def _pred_locked(self, attr: str) -> list:
        row = self.per_pred.get(attr)
        if row is None:
            row = self.per_pred[attr] = [0.0, 0, 0, 0]
        return row

    # -------------------------------------------------------------- charging

    def add_kernel(self, kernel: str, ms: float, h2d: int = 0,
                   d2h: int = 0, attr: str | None = None) -> None:
        """One device-kernel execution: fenced wall ms + transfer bytes,
        attributed to the current task's predicate (or `attr`)."""
        with self._lock:
            self.device_ms += ms
            self.h2d_bytes += int(h2d)
            self.d2h_bytes += int(d2h)
            self.kernels[kernel] = self.kernels.get(kernel, 0.0) + ms
            self.kernel_calls[kernel] = \
                self.kernel_calls.get(kernel, 0) + 1
            a = attr if attr is not None else \
                (self._attrs[-1] if self._attrs else "")
            if a.startswith("~"):
                a = a[1:]            # reverse reads charge the tablet
            if a:
                row = self._pred_locked(a)
                row[0] += ms
                row[2] += int(h2d) + int(d2h)

    def add_task(self, attr: str, edges: int) -> None:
        """One dispatched task completed (cache tiers + gate inside)."""
        with self._lock:
            self.tasks += 1
            self.edges += int(edges)
            row = self._pred_locked(attr)
            row[1] += int(edges)
            row[3] += 1

    def add_rows(self, n: int) -> None:
        with self._lock:
            self.rows += int(n)

    def attribute_pred_ms(self, attr: str, ms: float) -> None:
        """Re-attribute already-counted device ms to a predicate row
        WITHOUT touching the totals — for fused multi-predicate programs
        (mesh.plan) whose one launch is apportioned across hops after
        the per-hop edge counts are known."""
        if attr.startswith("~"):
            attr = attr[1:]
        if not attr or ms <= 0:
            return
        with self._lock:
            self._pred_locked(attr)[0] += ms

    def add_gate_wait(self, ms: float) -> None:
        with self._lock:
            self.gate_wait_ms += ms

    def add_compile(self, ms: float) -> None:
        """XLA compile wall ms this request triggered (the devprof
        jax.monitoring listener books it) — kept SEPARATE from device_ms
        so a first-touch compile doesn't poison the shape's EWMA
        regression baseline, while /debug/top?by=compile_ms still ranks
        the shapes paying for retraces."""
        with self._lock:
            self.compile_ms += ms

    def kernel_totals(self) -> tuple[dict[str, int], dict[str, int]]:
        """This node's device windows by kernel: (integer microseconds,
        window counts) — what /metrics carries per kernel."""
        with self._lock:
            return ({k: int(round(ms * 1e3))
                     for k, ms in self.kernels.items()},
                    dict(self.kernel_calls))

    def in_kernel(self) -> bool:
        """True while a kernel-timing window is open on this ledger — the
        dispatch gate consults it so injected device-latency faults are
        not charged a second time inside an enclosing kernel timer."""
        return self._kernel_depth > 0

    @contextlib.contextmanager
    def kernel_window(self):
        """Open a bare kernel-timing window (no charge of its own): the
        batcher's _timed_gate_run uses it so the gate's injected-fault
        charges are suppressed while the batched dt — which already
        contains them and is apportioned to every member — is measured."""
        with self._lock:
            self._kernel_depth += 1
        try:
            yield
        finally:
            with self._lock:
                self._kernel_depth -= 1

    def add_upload(self, nbytes: int) -> None:
        with self._lock:
            self.upload_bytes += int(nbytes)
            self.h2d_bytes += int(nbytes)

    def note(self, outcome: str, n: int = 1) -> None:
        """Count one cache/batch/shed/retry outcome."""
        with self._lock:
            self.outcomes[outcome] = self.outcomes.get(outcome, 0) + n

    # ---------------------------------------------------- remote assembly

    def merge_remote(self, addr: str, rec: dict) -> None:
        """Graft a callee's shipped record under this ledger (one entry
        per worker address; repeated RPCs to the same worker sum)."""
        if not rec:
            return
        with self._lock:
            g = self.groups.get(addr)
            if g is None:
                self.groups[addr] = dict(rec)
                # per-addr sub-dicts must be owned, not aliased
                for k in ("out", "pred", "kern"):
                    if k in rec:
                        self.groups[addr][k] = {
                            a: (list(v) if isinstance(v, list) else v)
                            for a, v in rec[k].items()}
                return
            for k in ("device_ms", "wall_ms", "gate_wait_ms",
                      "compile_ms"):
                g[k] = g.get(k, 0.0) + rec.get(k, 0.0)
            for k in ("h2d", "d2h", "upload", "edges", "rows", "tasks"):
                g[k] = g.get(k, 0) + rec.get(k, 0)
            for o, n in rec.get("out", {}).items():
                g.setdefault("out", {})
                g["out"][o] = g["out"].get(o, 0) + n
            for a, row in rec.get("pred", {}).items():
                g.setdefault("pred", {})
                cur = g["pred"].get(a)
                if cur is None:
                    g["pred"][a] = list(row)
                else:
                    for i in range(4):
                        cur[i] += row[i]
            for kn, ms in rec.get("kern", {}).items():
                g.setdefault("kern", {})
                g["kern"][kn] = g["kern"].get(kn, 0.0) + ms

    # ------------------------------------------------------------- totals

    def finish(self) -> None:
        self.wall_ms = (time.perf_counter() - self.t0) * 1e3

    def _local_locked(self) -> dict:
        return {"wall_ms": round(self.wall_ms, 3),
                "device_ms": round(self.device_ms, 3),
                "gate_wait_ms": round(self.gate_wait_ms, 3),
                "compile_ms": round(self.compile_ms, 3),
                "h2d": self.h2d_bytes, "d2h": self.d2h_bytes,
                "upload": self.upload_bytes,
                "edges": self.edges, "rows": self.rows,
                "tasks": self.tasks,
                "out": dict(self.outcomes),
                "pred": {a: [round(r[0], 3), r[1], r[2], r[3]]
                         for a, r in self.per_pred.items()},
                "kern": {k: round(v, 3) for k, v in self.kernels.items()}}

    def to_wire(self) -> bytes:
        """Compact shipped payload (a worker's local record only — the
        caller grafts it under its own groups map)."""
        with self._lock:
            return json.dumps(self._local_locked(),
                              separators=(",", ":")).encode()

    @staticmethod
    def from_wire(raw: bytes) -> dict:
        try:
            out = json.loads(raw.decode())
            return out if isinstance(out, dict) else {}
        except (ValueError, UnicodeDecodeError):
            return {}

    def to_dict(self) -> dict:
        """The assembled cluster-wide record: this node's local charges
        plus every shipped per-group record, with rolled-up totals.

        Physical costs (device ms, bytes, gate waits) SUM across local +
        groups — nobody else paid them. Logical counts (edges, tasks)
        take max(local, sum of groups): the querying node already
        attributes every dispatched task — including remote ones, whose
        traversed_edges ride the TaskResponse — so adding the workers'
        counts on top would double-book the same edges."""
        with self._lock:
            local = self._local_locked()
            groups = {a: dict(g) for a, g in self.groups.items()}
        total = dict(local)
        pred = {a: list(r) for a, r in local["pred"].items()}
        out = dict(local["out"])
        kern = dict(local["kern"])
        gsum = {k: 0 for k in ("edges", "tasks")}
        gpred: dict[str, list] = {}
        for g in groups.values():
            total["device_ms"] = round(
                total["device_ms"] + g.get("device_ms", 0.0), 3)
            total["gate_wait_ms"] = round(
                total["gate_wait_ms"] + g.get("gate_wait_ms", 0.0), 3)
            total["compile_ms"] = round(
                total["compile_ms"] + g.get("compile_ms", 0.0), 3)
            for k in ("h2d", "d2h", "upload", "rows"):
                total[k] += g.get(k, 0)
            for k in gsum:
                gsum[k] += g.get(k, 0)
            for o, n in g.get("out", {}).items():
                out[o] = out.get(o, 0) + n
            for a, row in g.get("pred", {}).items():
                cur = gpred.get(a)
                if cur is None:
                    gpred[a] = list(row)
                else:
                    for i in range(4):
                        cur[i] += row[i]
            for kn, ms in g.get("kern", {}).items():
                kern[kn] = round(kern.get(kn, 0.0) + ms, 3)
        for k in gsum:
            total[k] = max(total[k], gsum[k])
        for a, row in gpred.items():
            cur = pred.get(a)
            if cur is None:
                pred[a] = list(row)
            else:
                cur[0] += row[0]                 # device ms: physical
                cur[2] += row[2]                 # bytes: physical
                cur[1] = max(cur[1], row[1])     # edges: logical
                cur[3] = max(cur[3], row[3])     # tasks: logical
        total["pred"] = {a: [round(r[0], 3), r[1], r[2], r[3]]
                         for a, r in pred.items()}
        total["out"] = out
        total["kern"] = kern
        out2 = {"endpoint": self.endpoint, "shape": self.shape,
                "total": total, "local": local, "groups": groups}
        if self.tenant:
            out2["tenant"] = self.tenant
        if self.subs:
            out2["subs"] = list(self.subs)
        return out2


class _KernelTimer:
    """`with costs.kernel("csr.expand") as ck:` — times the enclosed
    device execution against the current ledger; a no-op (still yielding
    a settable object) when no ledger is armed. Bytes attach via
    ck.set(h2d=, d2h=). Exceptions still charge the elapsed time (a
    faulted upload consumed the wall clock it consumed).

    Several sites wrap a GATED call (the timer must bracket the lazy
    device value's host materialization, which happens after the gate
    releases), so dispatch-gate QUEUE time can fall inside the window.
    That wait is already booked as gate_wait_ms — counting it as device
    ms too would make every shape on a contended node look regressed —
    so the timer subtracts whatever gate wait the same ledger accrued
    during its window (same-thread nesting makes the delta exact;
    clamped at zero against concurrent hedge-thread waits)."""

    __slots__ = ("_lg", "_kernel", "_attr", "_t0", "_gw0", "h2d", "d2h",
                 "ms", "_pushed", "_stage")

    def __init__(self, kernel: str, attr: str | None = None,
                 stage: str = "dev.window") -> None:
        self._lg = _current.get()
        self._kernel = kernel
        self._attr = attr
        self.h2d = 0
        self.d2h = 0
        self.ms = 0.0          # charged wall ms, readable after exit
        self._pushed = False
        self._stage = _StageScope(stage)

    def __enter__(self):
        lg = self._lg
        if lg is not None:
            with lg._lock:
                lg._kernel_depth += 1
                self._gw0 = lg.gate_wait_ms
            # devprof armed: the kernel name IS the program family — the
            # thread-local stack lets the dispatch timeline and the XLA
            # compile listener attribute their records to "mesh.plan" /
            # "csr.expand" instead of the coarse gate class. One empty-
            # tuple truthiness check when the observatory is off.
            if devprof._PROFILERS:
                devprof.push_family(self._kernel)
                self._pushed = True
            self._t0 = time.perf_counter()
        # the window is also a stage of the request's clock (below): the
        # same two instants, read by the ledger and by the clock
        self._stage.__enter__()
        return self

    def set(self, h2d: int = 0, d2h: int = 0) -> None:
        self.h2d += int(h2d)
        self.d2h += int(d2h)

    def __exit__(self, *a):
        self._stage.__exit__()
        lg = self._lg
        if lg is not None:
            dt = (time.perf_counter() - self._t0) * 1e3
            if self._pushed:
                devprof.pop_family()
            with lg._lock:
                lg._kernel_depth -= 1
                waited = lg.gate_wait_ms - self._gw0
            self.ms = max(dt - waited, 0.0)
            lg.add_kernel(self._kernel, self.ms,
                          h2d=self.h2d, d2h=self.d2h, attr=self._attr)
        return False


def kernel(name: str, attr: str | None = None,
           stage: str = "dev.window") -> _KernelTimer:
    """`stage` is the request-clock stage the window opens in: the sites
    that split their window (dev.dispatch / dev.wait / dev.post) name the
    first part, every other site is one unsplit `dev.window`."""
    return _KernelTimer(name, attr, stage)


def note(outcome: str, n: int = 1) -> None:
    """Charge one outcome to the current ledger, if armed (the helper for
    modules that shouldn't know about ledgers: qcache, retry, gate)."""
    lg = _current.get()
    if lg is not None:
        lg.note(outcome, n)


def add_rows(n: int) -> None:
    lg = _current.get()
    if lg is not None:
        lg.add_rows(n)


def add_upload(nbytes: int) -> None:
    lg = _current.get()
    if lg is not None:
        lg.add_upload(nbytes)


def add_gate_wait(ms: float) -> None:
    lg = _current.get()
    if lg is not None:
        lg.add_gate_wait(ms)


# ---------------------------------------------------------------------------
# the stage clock: a request is, at every instant, in exactly one stage
# ---------------------------------------------------------------------------

_clock: contextvars.ContextVar["StageClock | None"] = \
    contextvars.ContextVar("dgt_stage_clock", default=None)


_get_ident = threading.get_ident
_now_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns

# A thread's CPU clock is a system call a read (CLOCK_THREAD_CPUTIME_ID has
# no vDSO path): 0.3 us on a plain kernel, 6 us where a sandbox handles
# system calls (gVisor) — read at all 23 switches of every request it took
# 13.6% of `khop-par22`'s requests a second there (PERF.md §6, PR 38). So
# one request in CPU_EVERY reads it, and counts itself in
# dgraph_stage_cpu_requests_total; the wall clock (vDSO, 0.07 us) stays on
# every request.
CPU_EVERY = 32
_cpu_turns = itertools.count()


def cpu_turn() -> bool:
    """Takes the next request's turn: True for the one request in
    CPU_EVERY whose stages read the CPU clock."""
    return next(_cpu_turns) % CPU_EVERY == 0


def clock() -> "StageClock | None":
    """The open stage clock of the request THIS thread is serving; None
    on a pool thread that merely runs in a copy of a request's context."""
    clk = _clock.get()
    return clk if clk is not None and clk._tid == _get_ident() else None


def _annotate(name: str):
    """Host-plane event of the profiler for one sampled segment (a no-op
    TraceMe unless a jax.profiler session is running)."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


class StageClock:
    """Where one request's time goes, by the program's own clock.

    The entry point that owns the request (HTTP do_POST, the gRPC handler,
    or Node.query called in-process) opens the clock with `with`; code
    below switches stages with `with costs.stage("parse"):`. A switch
    closes the running segment, charges its nanoseconds to its stage and
    starts the next, so segments never overlap and their sum is the
    request's time inside its entry point: self time needs no
    subtraction. Always on: a switch is one perf_counter_ns read and one
    dict add, no lock (only the opening thread switches: to a pool thread
    that runs in a copy of the request's context, clock() is None).

    For one request in CPU_EVERY (`cpu`: the owner's turn, or cpu_turn()
    here) `cpu` holds, beside `ns`, the CPU time the opening thread spent
    in each stage (time.thread_time_ns, one more read a switch); for the
    others it is None. Wall less CPU of a stage that does not block by
    design is time the thread was runnable and not running — queued for
    the interpreter, or for a core. CPU burnt in C with the interpreter
    released (numpy, the jit call's C++) counts as the thread's work, so
    the CPU of all threads' stages together can pass one core.

    `before` holds what happened to the request before its owner could
    open a clock, as (stage, start perf_counter_ns, start thread_time_ns
    or None) in order: each segment runs to the next one's start, the
    last to the clock's opening; None says the segment crossed threads
    and has no CPU time by definition. The HTTP handler passes
    `http.accept` and `http.head` (api/http.py); the root span is moved
    back to the first one's start, so the segments are its children like
    every other.

    `root` is the request's root span — the ONE sampling decision. When
    it is a real span, every segment is also a child span of kind
    "stage" (`cpu_us` in its attrs where the CPU clock was read; parent =
    the span current when the segment opened; handed to the tracer in one
    piece when the clock closes) and a
    jax.profiler.TraceAnnotation("dgraph.<stage>"), so /debug/traces and
    the profiler's host plane read this same clock. Only segments are
    annotated, never the spans that enclose them, and not the `before`
    segments: they were over when the clock opened.

    Closing flushes integer microseconds to
    dgraph_stage_us_total{stage=} and counts the request in
    dgraph_stage_requests_total; a request that read the CPU clock also
    flushes dgraph_stage_cpu_us_total{stage=} and counts itself in
    dgraph_stage_cpu_requests_total."""

    __slots__ = ("ns", "cpu", "root", "claimed", "_metrics", "_tid", "_cur",
                 "_t", "_c", "_segs", "_ann", "_parent", "_wall0", "_t0",
                 "_before", "_token")

    def __init__(self, first: str, root, metrics=None, before=(),
                 cpu: bool | None = None) -> None:
        self.ns: dict[str, int] = {}
        self.cpu: dict[str, int] | None = \
            {} if (cpu_turn() if cpu is None else cpu) else None
        self.root = root
        self.claimed = False      # Node.query took `root` as its own span
        self._metrics = metrics
        self._tid = _get_ident()
        self._cur = first
        self._before = before
        # sampled only: closed segments (name, parent span id, start ns,
        # end ns, cpu ns), the running segment's annotation and parent
        # span id
        self._segs: list | None = [] if root else None
        self._ann = None
        self._parent = ""

    def __enter__(self) -> "StageClock":
        self._token = _clock.set(self)
        self.root.__enter__()
        self._wall0 = time.time()
        self._t = self._t0 = _now_ns()
        self._c = _cpu_ns() if self.cpu is not None else 0
        if self._before:
            self._backfill()
        if self._segs is not None:
            self._open_segment(self._cur)
        return self

    def _backfill(self) -> None:
        """Charge the `before` segments, and start the root span where the
        first of them did."""
        before, segs = self._before, self._segs
        last = len(before) - 1
        for i, (name, a, c0) in enumerate(before):
            b = self._t0 if i == last else before[i + 1][1]
            self.ns[name] = self.ns.get(name, 0) + b - a
            dc = None
            if self.cpu is not None:
                # only the last segment ends on this thread's CPU clock
                dc = self._c - c0 if i == last and c0 is not None else 0
                self.cpu[name] = self.cpu.get(name, 0) + dc
            if segs is not None:
                segs.append((name, self.root.span_id, a, b, dc))
        if segs is not None:
            self.root.backdate((self._t0 - before[0][1]) * 1e-9)

    def __exit__(self, et, ev, tb):
        self.switch("")
        if self._segs:
            self._ship_segments()
        self.root.__exit__(et, ev, tb)
        _clock.reset(self._token)
        m = self._metrics
        if m is not None:
            m.keyed("dgraph_stage_us_total", labels=("stage",)).inc_many(
                {k: v // 1000 for k, v in self.ns.items()})
            m.counter("dgraph_stage_requests_total").inc()
            if self.cpu is not None:
                m.keyed("dgraph_stage_cpu_us_total",
                        labels=("stage",)).inc_many(
                    {k: v // 1000 for k, v in self.cpu.items()})
                m.counter("dgraph_stage_cpu_requests_total").inc()
        return False

    def switch(self, name: str) -> str:
        """Close the running segment, start `name`; returns the stage that
        was running. For the opening thread only (clock() sees to it).
        A full collection that starts in here switches the clock from
        inside this call (GcPauses): the clock's own fields are set before
        the sampled part so that it finds a clock it can switch, and what
        it charges to the wrong stage is less than its own pause."""
        now = _now_ns()
        prev = self._cur
        t0 = self._t
        ns = self.ns
        ns[prev] = ns.get(prev, 0) + now - t0
        dc = None
        cpu = self.cpu
        if cpu is not None:
            c = _cpu_ns()
            dc = c - self._c
            cpu[prev] = cpu.get(prev, 0) + dc
            self._c = c
        self._cur = name
        self._t = now
        if self._segs is not None:
            ann, self._ann = self._ann, None
            if ann is not None:
                ann.__exit__(None, None, None)
            self._segs.append((prev, self._parent, t0, now, dc))
            if name:
                self._open_segment(name)
        return prev

    def _open_segment(self, name: str) -> None:
        self._parent = (otrace.current() or self.root).span_id
        self._ann = _annotate("dgraph." + name)
        self._ann.__enter__()

    def _ship_segments(self) -> None:
        """The closed segments as finished spans of the root's trace."""
        root, tracer = self.root, self.root.tracer
        wall0, t0 = self._wall0, self._t0
        tracer.add_remote([
            {"trace_id": root.trace_id, "span_id": tracer._new_id(),
             "parent_id": parent, "name": name, "kind": "stage",
             "proc": root.proc, "start": wall0 + (a - t0) * 1e-9,
             "dur": round((b - a) * 1e-9, 9),
             "attrs": {} if dc is None else {"cpu_us": dc // 1000}}
            for name, parent, a, b, dc in self._segs])

    def server_latency(self) -> dict:
        """The reference's Latency split, from the stages closed so far:
        parsing = `parse`; processing = `plan` + `exec*` + `dev.*` +
        `batch.wait` + `gate.wait`; encoding = `encode`."""
        ns = self.ns
        return {"parsing_ns": ns.get("parse", 0),
                "processing_ns": sum(
                    v for k, v in ns.items()
                    if k == "plan" or k.startswith(
                        ("exec", "dev.", "batch.", "gate."))),
                "encoding_ns": ns.get("encode", 0)}


class _StageScope:
    """`with costs.stage("parse"):` — the request is in `parse` until the
    block ends (also by an exception), then back in the stage it came
    from. One contextvar read when no clock is open."""

    __slots__ = ("_name", "_clk", "_prev")

    def __init__(self, name: str) -> None:
        self._name = name

    def __enter__(self):
        clk = self._clk = clock()
        if clk is not None:
            self._prev = clk.switch(self._name)
        return self

    def __exit__(self, *a):
        if self._clk is not None:
            self._clk.switch(self._prev)
        return False


stage = _StageScope


class GcPauses:
    """The pauses of the interpreter's collector, by generation: one
    gc.callbacks hook (the interpreter calls it with "start" and "stop"
    around every collection, on the thread whose allocation set it off;
    collections never nest, so one start stamp is enough).

    The hook takes no lock and calls nothing that does — a collection
    can start at any allocation, also inside Metrics._lock on the same
    thread: it adds to plain ints, which publish() copies into
    dgraph_gc_pause_us_total{generation=} and
    dgraph_gc_collections_total{generation=} when /metrics is rendered.

    A full collection (generation 2) is also a stage of the request whose
    thread it ran on: that thread's open clock, if any, is in stage `gc`
    for the pause (on a sampled request with its TraceAnnotation
    "dgraph.gc", so an idle gap of the device in a profile can carry its
    cause, and an event `gc` on the root span). Every other thread stands
    still meanwhile, and shows the pause as wall time without CPU in
    whatever stage it was in."""

    __slots__ = ("pause_ns", "collections", "_t0", "_clk", "_prev")

    def __init__(self) -> None:
        self.pause_ns = [0, 0, 0]
        self.collections = [0, 0, 0]
        self._t0 = 0
        self._clk: StageClock | None = None
        self._prev = ""

    def __call__(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            if gen == 2:
                clk = self._clk = clock()
                if clk is not None:
                    self._prev = clk.switch("gc")
            self._t0 = _now_ns()
            return
        dt = _now_ns() - self._t0
        self.pause_ns[gen] += dt
        self.collections[gen] += 1
        clk = self._clk
        if clk is not None:
            self._clk = None
            clk.switch(self._prev)
            clk.root.event("gc", generation=gen,
                           collected=info.get("collected", 0),
                           ms=round(dt * 1e-6, 3))

    def install(self) -> None:
        if self not in gc.callbacks:
            gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def publish(self, metrics) -> None:
        pause = metrics.keyed("dgraph_gc_pause_us_total",
                              labels=("generation",))
        count = metrics.keyed("dgraph_gc_collections_total",
                              labels=("generation",))
        for gen in range(3):
            pause.set(str(gen), self.pause_ns[gen] // 1000)
            count.set(str(gen), self.collections[gen])


# the process has one collector, so one record of its pauses: `serve`
# installs the hook at start-up; every node of the process publishes it
GC_PAUSES = GcPauses()


# ---------------------------------------------------------------------------
# the /debug/top sliding-window profiler
# ---------------------------------------------------------------------------

class CostBook:
    """Bounded window of completed cost records + per-shape EWMA
    baselines.

    record() returns a regression flag dict when the record's device_ms
    exceeds `regression_factor` x the shape's warmed baseline — the
    caller (Node.query) routes it into the slow-query ring, which is how
    a shape that regressed from 2ms to 40ms surfaces even under a 500ms
    --slow_query_ms threshold. Baselines need `MIN_SAMPLES` observations
    before they flag (a cold shape's first compile is not a regression).
    """

    MIN_SAMPLES = 8
    EWMA_ALPHA = 0.2
    # baseline floor (ms): a pure-host shape's baseline is ~0, and 4 x ~0
    # would flag the first microsecond of device work — regressions are
    # only meaningful above this much device time
    BASELINE_FLOOR_MS = 0.05

    def __init__(self, keep: int = 4096,
                 regression_factor: float = 4.0) -> None:
        from collections import OrderedDict

        self._lock = threading.Lock()
        self._ring: deque[tuple[float, str, str, str, dict]] = \
            deque(maxlen=keep)
        # shape -> [ewma_device_ms, samples]; LRU-bounded — shapes are
        # raw DQL text, and clients that inline literals instead of
        # variables mint a new shape per request, so an unbounded map
        # would grow RSS forever on a long-running node
        self._baseline: "OrderedDict[str, list]" = OrderedDict()
        self._baseline_cap = max(int(keep), 16)
        self.regression_factor = float(regression_factor)
        self.flagged = 0

    def record(self, shape: str, endpoint: str, trace_id: str,
               rec: dict) -> dict | None:
        """Admit one assembled record (rec = CostLedger.to_dict()).
        Returns the regression-flag entry or None."""
        total = rec.get("total", {})
        dms = float(total.get("device_ms", 0.0))
        now = time.monotonic()
        flag = None
        with self._lock:
            self._ring.append((now, shape, endpoint, trace_id, rec))
            b = self._baseline.get(shape)
            if b is None:
                self._baseline[shape] = [dms, 1]
                while len(self._baseline) > self._baseline_cap:
                    self._baseline.popitem(last=False)
            else:
                self._baseline.move_to_end(shape)
                if b[1] >= self.MIN_SAMPLES and \
                        dms > self.regression_factor * \
                        max(b[0], self.BASELINE_FLOOR_MS):
                    self.flagged += 1
                    flag = {"reason": "cost_regression",
                            "shape": shape[:200],
                            "endpoint": endpoint,
                            "trace_id": trace_id,
                            "device_ms": round(dms, 3),
                            "baseline_ms": round(b[0], 3),
                            "factor": round(dms / max(b[0], 1e-3), 1),
                            "edges": total.get("edges", 0),
                            "bytes": total.get("h2d", 0)
                            + total.get("d2h", 0)}
                # the EWMA keeps learning (a real shift becomes the new
                # baseline instead of flagging forever)
                b[0] = (1 - self.EWMA_ALPHA) * b[0] \
                    + self.EWMA_ALPHA * dms
                b[1] += 1
        return flag

    def baseline(self, shape: str) -> tuple[float, int]:
        with self._lock:
            b = self._baseline.get(shape)
            return (b[0], b[1]) if b is not None else (0.0, 0)

    def last(self) -> dict | None:
        """The newest assembled record, per-group sub-records included."""
        with self._lock:
            if not self._ring:
                return None
            _ts, shape, ep, tid, rec = self._ring[-1]
            return {"shape": shape, "endpoint": ep, "trace_id": tid,
                    **rec}

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def top(self, window_s: float = 60.0, by: str = "device_ms",
            group: str = "shape", n: int = 20,
            endpoint: str | None = None) -> dict:
        """Rank shapes/predicates/endpoints by summed cost over the
        trailing window. The /debug/top payload. `endpoint` restricts the
        window to records from that endpoint first (?endpoint=live ranks
        standing-subscription re-evals by shape, next to — but separable
        from — foreground query load)."""
        cutoff = time.monotonic() - max(window_s, 0.0)
        agg: dict[str, dict] = {}
        seen = 0
        with self._lock:
            entries = [e for e in self._ring
                       if e[0] >= cutoff
                       and (endpoint is None or e[2] == endpoint)]
            baselines = {s: (b[0], b[1])
                         for s, b in self._baseline.items()}
        for _ts, shape, ep, tid, rec in entries:
            total = rec.get("total", {})
            seen += 1
            if group == "sub":
                # per-subscription attribution (ISSUE 19 satellite of
                # the PR 18 leftover): a live re-eval record carries the
                # ids of every subscription its coalesced group served —
                # the shared eval's cost apportions equally among them,
                # so 10k standing copies of one feed don't multiply the
                # booked device time
                sids = rec.get("subs") or ()
                if not sids:
                    continue
                share = 1.0 / len(sids)
                for sid in sids:
                    a = agg.setdefault(sid, {
                        "device_ms": 0.0, "wall_ms": 0.0,
                        "compile_ms": 0.0, "edges": 0.0, "bytes": 0.0,
                        "records": 0, "shape": ""})
                    a["device_ms"] = round(
                        a["device_ms"]
                        + float(total.get("device_ms", 0.0)) * share, 3)
                    a["wall_ms"] = round(
                        a["wall_ms"]
                        + float(total.get("wall_ms", 0.0)) * share, 3)
                    a["compile_ms"] = round(
                        a["compile_ms"]
                        + float(total.get("compile_ms", 0.0)) * share, 3)
                    a["edges"] = round(
                        a["edges"]
                        + int(total.get("edges", 0)) * share, 1)
                    a["bytes"] = round(
                        a["bytes"] + (int(total.get("h2d", 0))
                                      + int(total.get("d2h", 0)))
                        * share, 1)
                    a["records"] += 1
                    a["shape"] = shape[:200]
                continue
            if group == "pred":
                for attr, row in total.get("pred", {}).items():
                    a = agg.setdefault(attr, {
                        "device_ms": 0.0, "edges": 0, "bytes": 0,
                        "tasks": 0, "records": 0})
                    a["device_ms"] = round(a["device_ms"] + row[0], 3)
                    a["edges"] += row[1]
                    a["bytes"] += row[2]
                    a["tasks"] += row[3]
                    a["records"] += 1
                continue
            if group == "tenant":
                # /debug/top?group=tenant — per-namespace attribution
                # (ISSUE 20): every record is stamped with its minting
                # tenant; unstamped records are the default namespace
                gkey = rec.get("tenant") or "default"
            else:
                gkey = ep if group == "endpoint" else shape
            a = agg.setdefault(gkey, {
                "device_ms": 0.0, "wall_ms": 0.0, "compile_ms": 0.0,
                "edges": 0, "bytes": 0, "records": 0, "trace_id": ""})
            a["device_ms"] = round(
                a["device_ms"] + float(total.get("device_ms", 0.0)), 3)
            a["wall_ms"] = round(
                a["wall_ms"] + float(total.get("wall_ms", 0.0)), 3)
            a["compile_ms"] = round(
                a["compile_ms"] + float(total.get("compile_ms", 0.0)), 3)
            a["edges"] += int(total.get("edges", 0))
            a["bytes"] += int(total.get("h2d", 0)) + \
                int(total.get("d2h", 0))
            a["records"] += 1
            if tid:
                a["trace_id"] = tid      # newest sampled exemplar wins
        rank_key = {"device_ms": "device_ms", "edges": "edges",
                    "bytes": "bytes", "wall_ms": "wall_ms",
                    "compile_ms": "compile_ms"}.get(by, "device_ms")
        if group == "pred" and rank_key in ("wall_ms", "compile_ms"):
            rank_key = "device_ms"     # pred rows carry neither
        ranked = sorted(agg.items(), key=lambda kv: kv[1].get(rank_key, 0),
                        reverse=True)[: max(n, 1)]
        out = []
        for k, v in ranked:
            row = {"key": k[:200], **v}
            if group == "shape":
                bl = baselines.get(k)
                if bl is not None:
                    row["baseline_device_ms"] = round(bl[0], 3)
                    row["baseline_samples"] = bl[1]
                    mean = v["device_ms"] / max(v["records"], 1)
                    row["regressed"] = bool(
                        bl[1] >= self.MIN_SAMPLES
                        and mean > self.regression_factor
                        * max(bl[0], self.BASELINE_FLOOR_MS))
            out.append(row)
        return {"window_s": window_s, "by": by, "group": group,
                "endpoint": endpoint,
                "records_in_window": seen, "flagged_total": self.flagged,
                "top": out}
