"""Device-runtime observatory (ISSUE 19): XLA compile/retrace tracking,
HBM telemetry, and a dispatch-timeline utilization profiler.

The gap after PR 13: the cost ledger answers "what did THIS query cost"
in wall/device ms, but not WHY — when a mesh run loses to the host,
nothing on /debug decomposes that into compiles vs queue gaps vs kernel
time. Three surfaces close it:

  * compile observatory — every jitted-program build site (mesh_exec's
    program cache, dist.py's lru builders) notes its build through a
    registering seam that attributes build count + triggering shape
    signature to a named PROGRAM FAMILY (the costs.kernel vocabulary:
    mesh.plan, csr.expand, batch.recurse, ...). Real XLA compile wall
    ms rides jax.monitoring's backend_compile event listener, attributed
    to the family on the profiler's thread-local stack (pushed by
    costs._KernelTimer while armed) — `jax.jit` is lazy, so timing the
    build call site would measure nothing. A family recompiling under
    shape churn within a window is a RETRACE STORM: flagged into the
    PR 13 regression slowlog (root="retrace_storm") and counted on
    dgraph_xla_retrace_storms_total. GET /debug/compiles serves
    per-family builds/compiles/cumulative ms/last-trigger shapes plus
    the live program-cache sizes.
  * HBM telemetry — per-dispatch live/peak device-byte sampling:
    jax device.memory_stats() where the backend reports it (TPU/GPU;
    capability probed once — CPU returns None), the ResidencyManager's
    tier accounting as the always-available spine. High-water marks per
    tier land on dgraph_devprof_hbm_highwater_bytes{tier=...}; peak
    crossing the --device_budget_mb headroom raises a pressure flag
    (counter + span event on the causing dispatch).
  * dispatch timeline — a bounded ring of (program family, queue-entry,
    launch, fence-complete, bytes moved) records fed from
    DispatchGate.run — the one chokepoint every device dispatch (solo
    task, DeviceBatcher leader, analytics, mesh program) passes through
    — exported as Chrome trace-event JSON at /debug/timeline (same
    format as /debug/traces/<id>, loadable in Perfetto) plus the
    derived dgraph_device_utilization / queue-gap / dispatch-ms meters.

Disarm contract (--no_devprof): zero overhead by construction. The gate
checks one attribute (None), the kernel timer checks one module tuple
(empty), and the jax.monitoring listener is never even registered until
the first profiler arms — pre-19 behavior is byte-identical.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from ..utils import locks

# -- global registration seam ------------------------------------------------
#
# Per-node profilers attach directly where a node owns the seam
# (DispatchGate.profiler, MeshExecutor._prof). Process-global build sites
# (dist.py's lru_cache program builders) fan out through this
# copy-on-write tuple instead: reads are one load of an (almost always
# empty) tuple, writes swap the whole tuple under the lock.

_PROFILERS: tuple = ()
_reg_lock = threading.Lock()
_listener_installed = False

# thread-local program-family stack: costs._KernelTimer pushes its kernel
# name here while any profiler is armed, so compile events and timeline
# records pick up the fine-grained family ("mesh.plan", "csr.expand")
# instead of the coarse gate class
_tls = threading.local()


def armed() -> bool:
    return bool(_PROFILERS)


def push_family(name: str) -> None:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    st.append(name)


def pop_family() -> None:
    st = getattr(_tls, "stack", None)
    if st:
        st.pop()


def current_family(default: str | None = None) -> str | None:
    st = getattr(_tls, "stack", None)
    return st[-1] if st else default


def note_build(family: str, key=None) -> None:
    """A process-global build site (dist.py lru builder) constructed one
    jitted program. One tuple load when nothing is armed."""
    for p in _PROFILERS:
        p.on_build(family, key)


def register(p: "DevProfiler") -> None:
    global _PROFILERS
    with _reg_lock:
        if p not in _PROFILERS:
            _PROFILERS = _PROFILERS + (p,)
    _install_listener_once()


def unregister(p: "DevProfiler") -> None:
    global _PROFILERS
    with _reg_lock:
        _PROFILERS = tuple(x for x in _PROFILERS if x is not p)


# -- jax.monitoring compile listener -----------------------------------------
#
# jax.jit is LAZY: tracing + XLA compilation happen at the first call with
# a new signature, inside the dispatch — not at the build site. The only
# faithful compile-ms source is jax.monitoring's event-duration stream
# (/jax/core/compile/backend_compile_duration fires once per XLA
# compile). Registered exactly once, on the FIRST profiler arm ever —
# a --no_devprof process never registers it — and the callback's first
# check is the armed tuple, so a later disarm costs one load per compile.

_COMPILE_EVENT = "backend_compile_duration"
# persistent compilation cache outcomes (one event per compile request
# that consulted the cache): a warm restart should show hits and no misses
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}


def _on_duration_event(event: str, duration: float, **kw) -> None:
    profs = _PROFILERS
    if not profs or duration is None:
        return
    if not event.endswith(_COMPILE_EVENT):
        return
    ms = float(duration) * 1e3
    fam = current_family("unattributed")
    for p in profs:
        p.on_compile(fam, ms)
    from . import costs

    lg = costs.current()
    if lg is not None:
        lg.add_compile(ms)


def _on_event(event: str, **kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        for p in _PROFILERS:
            p.on_cache_event(outcome)


def _install_listener_once() -> None:
    global _listener_installed
    if _listener_installed:
        return
    _listener_installed = True
    try:
        from jax import monitoring
    except Exception:
        return
    try:
        monitoring.register_event_duration_secs_listener(_on_duration_event)
        monitoring.register_event_listener(_on_event)
    except Exception:
        pass


def _sig(key) -> str:
    """Compact shape signature of one build trigger."""
    if key is None:
        return ""
    s = repr(key)
    return s if len(s) <= 200 else s[:197] + "..."


class DevProfiler:
    """One node's device-runtime observatory (all three surfaces).

    Constructed by Node when devprof is on, attached as
    DispatchGate.profiler / MeshExecutor._prof and registered on the
    module fan-out; never constructed under --no_devprof.
    """

    # retrace-storm detection: >= STORM_MIN_BUILDS compile/build events
    # of ONE family with >= STORM_MIN_SHAPES distinct trigger signatures
    # inside STORM_WINDOW_S, flagged at most once per window per family.
    # (A fresh program cache warming N distinct keys is normal; churn
    # past these floors means shapes are NOT converging to the cache.)
    STORM_WINDOW_S = 30.0
    STORM_MIN_BUILDS = 4
    STORM_MIN_SHAPES = 3
    # HBM pressure: peak over this fraction of the device budget
    PRESSURE_HEADROOM = 0.9
    # utilization gauge refresh cadence (dispatches)
    UTIL_REFRESH = 32

    def __init__(self, metrics, slow_log=None, budget_bytes: int = 0,
                 residency=None, ring_size: int = 2048) -> None:
        self._m = metrics
        self._slow_log = slow_log
        self._residency = residency
        self.budget_bytes = int(budget_bytes)
        self._lock = locks.Lock("devprof.DevProfiler._lock")
        # family -> {"builds", "compiles", "compile_ms", "storms",
        #            "shapes": deque[(mono_ts, sig)], "last": str,
        #            "storm_at": float}
        self._fams: dict[str, dict] = {}
        # timeline ring: (seq, mono_ts, family, klass, queue_ms, run_ms,
        #                 bytes_moved)
        self._ring: deque = deque(maxlen=max(int(ring_size), 16))
        self._seq = 0
        self._busy_ms = 0.0              # cumulative fenced run ms
        self._born = time.monotonic()
        self._cache_probes: list[tuple[str, object]] = []
        self._pcache = {"hits": 0, "misses": 0}   # persistent compile cache
        self._hbm_capable: bool | None = None
        self._high_water: dict[str, int] = {}
        self._pressure_latched = False
        # metric objects cached once — record_dispatch is the hot path
        self._c_compiles = metrics.counter("dgraph_xla_compiles_total")
        self._c_storms = metrics.counter(
            "dgraph_xla_retrace_storms_total")
        self._c_disp = metrics.counter("dgraph_devprof_dispatches_total")
        self._c_pressure = metrics.counter(
            "dgraph_devprof_hbm_pressure_total")
        self._g_util = metrics.counter("dgraph_device_utilization")
        self._g_budget = metrics.counter("dgraph_devprof_hbm_budget_bytes")
        self._k_hbm = metrics.keyed("dgraph_devprof_hbm_highwater_bytes",
                                    labels=("tier",))
        self._h_compile = metrics.histogram("dgraph_xla_compile_ms")
        self._h_gap = metrics.histogram("dgraph_device_queue_gap_ms")
        self._h_disp = metrics.histogram("dgraph_device_dispatch_ms")
        self._g_budget.set(self.budget_bytes)

    # -- compile observatory -------------------------------------------------

    def _fam_locked(self, family: str) -> dict:
        f = self._fams.get(family)
        if f is None:
            f = self._fams[family] = {
                "builds": 0, "compiles": 0, "compile_ms": 0.0,
                "storms": 0, "shapes": deque(maxlen=64), "last": "",
                "storm_at": 0.0}
        return f

    def on_build(self, family: str, key=None) -> None:
        """One program-cache miss built a new jitted program (mesh_exec
        stores, dist lru builders) — the shape signature is the cache
        key that missed."""
        self._note_event(family, _sig(key), compile_ms=None)

    def on_compile(self, family: str, ms: float) -> None:
        """One real XLA compile completed (jax.monitoring listener). The
        trigger signature is synthetic — each compile of an already-seen
        family IS a fresh signature by definition (the jit cache
        missed)."""
        self._c_compiles.inc()
        self._h_compile.observe(ms)
        self._note_event(family, None, compile_ms=ms)

    def on_cache_event(self, outcome: str) -> None:
        """One compile request hit or missed JAX's persistent compilation
        cache (jax.monitoring event listener)."""
        with self._lock:
            self._pcache[outcome] += 1

    def _note_event(self, family: str, sig: str | None,
                    compile_ms: float | None) -> None:
        now = time.monotonic()
        storm = None
        with self._lock:
            f = self._fam_locked(family)
            if compile_ms is None:
                f["builds"] += 1
            else:
                f["compiles"] += 1
                f["compile_ms"] += compile_ms
                sig = f"compile#{f['compiles']}"
            if sig:
                f["last"] = sig
            f["shapes"].append((now, sig or ""))
            recent = [s for t, s in f["shapes"]
                      if now - t <= self.STORM_WINDOW_S]
            if (len(recent) >= self.STORM_MIN_BUILDS
                    and len(set(recent)) >= self.STORM_MIN_SHAPES
                    and now - f["storm_at"] > self.STORM_WINDOW_S):
                f["storm_at"] = now
                f["storms"] += 1
                storm = {"family": family, "builds_in_window": len(recent),
                         "distinct_shapes": len(set(recent)),
                         "window_s": self.STORM_WINDOW_S,
                         "last_shape": f["last"]}
        if storm is not None:
            self._c_storms.inc()
            if self._slow_log is not None:
                self._slow_log.record({
                    "ts": time.strftime("%Y-%m-%dT%H:%M:%S",
                                        time.gmtime()),
                    "root": "retrace_storm",
                    "reason": "retrace_storm",
                    "elapsed_ms": 0.0,
                    **storm})

    def add_cache_probe(self, name: str, fn) -> None:
        """Register a live program-cache size callable for
        /debug/compiles (mesh_exec._progs, dist lru caches, ops jit
        caches). Probes must be cheap and exception-safe is handled
        here."""
        with self._lock:
            self._cache_probes.append((name, fn))

    def compiles_snapshot(self) -> dict:
        """GET /debug/compiles payload."""
        with self._lock:
            fams = {
                name: {"builds": f["builds"], "compiles": f["compiles"],
                       "compile_ms": round(f["compile_ms"], 3),
                       "storms": f["storms"], "last_shape": f["last"],
                       "recent_shapes": [s for _t, s in f["shapes"]][-8:]}
                for name, f in sorted(self._fams.items())}
            probes = list(self._cache_probes)
            pcache = dict(self._pcache)
        caches = {}
        for name, fn in probes:
            try:
                v = fn()
            except Exception:
                caches[name] = -1
                continue
            if isinstance(v, dict):
                # one probe may report a whole group of caches (the ops
                # modules' JIT_PROGRAMS registries, keyed by family)
                for k, x in v.items():
                    caches[str(k)] = int(x)
            else:
                caches[name] = int(v)
        return {
            "enabled": True,
            "families": fams,
            "cache_sizes": caches,
            "persistent_cache": pcache,
            "compiles": self._c_compiles.value,
            "compile_ms_total": round(sum(
                f["compile_ms"] for f in fams.values()), 3),
            "retrace_storms": self._c_storms.value,
        }

    # -- HBM telemetry -------------------------------------------------------

    def _probe_hbm_locked(self) -> None:
        """One-time capability probe: device.memory_stats() returns a
        dict on TPU/GPU backends and None on CPU."""
        self._hbm_capable = False
        try:
            import jax

            for d in jax.local_devices():
                if d.memory_stats() is not None:
                    self._hbm_capable = True
                    break
        except Exception:
            pass

    def _device_bytes(self) -> tuple[int, int]:
        """(live, peak) device bytes from the backend, 0s when the
        backend doesn't report them."""
        if not self._hbm_capable:
            return 0, 0
        live = peak = 0
        try:
            import jax

            for d in jax.local_devices():
                st = d.memory_stats() or {}
                live += int(st.get("bytes_in_use", 0))
                peak += int(st.get("peak_bytes_in_use",
                                   st.get("bytes_in_use", 0)))
        except Exception:
            return 0, 0
        return live, peak

    def _sample_hbm_locked(self) -> dict | None:
        """Per-dispatch tier sample: returns a pressure event dict when
        peak newly crosses the budget headroom, else None. The keyed
        high-water gauge is only touched on a new high-water mark, so
        the steady-state cost is dict lookups."""
        if self._hbm_capable is None:
            self._probe_hbm_locked()
        tiers: dict[str, int] = {}
        live, peak = self._device_bytes()
        if live or peak:
            tiers["device"] = peak or live
        r = self._residency
        if r is not None:
            try:
                tiers["hbm"] = int(r.usage())
                tiers["host"] = int(r.host_bytes())
            except Exception:
                pass
        hw_peak = 0
        for tier, v in tiers.items():
            if v > self._high_water.get(tier, -1):
                self._high_water[tier] = v
                self._k_hbm.set(tier, v)
        hw_peak = max(tiers.get("device", 0), tiers.get("hbm", 0))
        if not self.budget_bytes:
            return None
        threshold = self.PRESSURE_HEADROOM * self.budget_bytes
        if hw_peak > threshold:
            if not self._pressure_latched:
                self._pressure_latched = True
                return {"peak_bytes": hw_peak,
                        "budget_bytes": self.budget_bytes,
                        "headroom": self.PRESSURE_HEADROOM}
        elif hw_peak < 0.8 * self.budget_bytes:
            self._pressure_latched = False   # re-arm after back-off
        return None

    def hbm_snapshot(self) -> dict:
        with self._lock:
            return {"capable": bool(self._hbm_capable),
                    "budget_bytes": self.budget_bytes,
                    "high_water": dict(self._high_water),
                    "pressure_events": self._c_pressure.value}

    # -- dispatch timeline ---------------------------------------------------

    def record_dispatch(self, klass: str | None, t_queue: float,
                        t_launch: float, t_fence: float,
                        bytes_moved: int = 0) -> None:
        """One gated device dispatch completed (called from
        DispatchGate.run's finally — every solo task, batch leader,
        analytics run, and mesh program passes exactly once). Timestamps
        are perf_counter values from the gate itself."""
        family = current_family(None) or (klass or "device")
        queue_ms = max((t_launch - t_queue) * 1e3, 0.0)
        run_ms = max((t_fence - t_launch) * 1e3, 0.0)
        pressure = None
        with self._lock:
            self._seq += 1
            seq = self._seq
            self._ring.append((seq, time.monotonic(), family,
                               klass or "", queue_ms, run_ms,
                               int(bytes_moved)))
            self._busy_ms += run_ms
            pressure = self._sample_hbm_locked()
            refresh = seq % self.UTIL_REFRESH == 0
        self._c_disp.inc()
        self._h_gap.observe(queue_ms)
        self._h_disp.observe(run_ms)
        if refresh:
            self._refresh_utilization()
        if pressure is not None:
            self._c_pressure.inc()
            from . import otrace

            otrace.event("hbm_pressure", family=family, **pressure)

    def _refresh_utilization(self) -> None:
        """Derived occupancy gauge: fenced device-busy ms over the
        trailing ring window, as a 0-100 percentage (can exceed 100 on a
        gate wider than 1 — concurrent dispatches overlap)."""
        with self._lock:
            if not self._ring:
                self._g_util.set(0.0)
                return
            oldest = self._ring[0][1]
            busy = sum(r[5] for r in self._ring)
        wall_ms = max((time.monotonic() - oldest) * 1e3, 1e-3)
        self._g_util.set(round(min(busy / wall_ms, 10.0) * 100.0, 2))

    def timeline_snapshot(self, n: int = 256) -> list[dict]:
        with self._lock:
            recs = list(self._ring)[-max(int(n), 1):]
        return [{"seq": s, "ts": ts, "family": fam, "klass": kl,
                 "queue_ms": round(qm, 3), "run_ms": round(rm, 3),
                 "bytes": b}
                for s, ts, fam, kl, qm, rm, b in recs]

    def timeline_chrome(self) -> dict:
        """The /debug/timeline payload: Chrome trace-event JSON in the
        same envelope as /debug/traces/<id> (obs/otrace.chrome_trace),
        so it drops into the existing Perfetto workflow. Two tracks per
        record: queue wait and fenced execution."""
        with self._lock:
            recs = list(self._ring)
            busy = self._busy_ms
        events: list[dict] = [
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "device.queue"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 2,
             "args": {"name": "device.run"}},
        ]
        if recs:
            t0 = recs[0][1]
            for seq, ts, fam, kl, qm, rm, b in recs:
                # ts is the FENCE time (appended at completion): rebase
                # launch = fence - run, queue-entry = launch - queue
                fence_us = (ts - t0) * 1e6
                launch_us = fence_us - rm * 1e3
                queue_us = launch_us - qm * 1e3
                args = {"seq": seq, "family": fam, "klass": kl,
                        "bytes": b}
                if qm > 0:
                    events.append({"name": f"{fam} (queued)", "ph": "X",
                                   "pid": 1, "tid": 1,
                                   "ts": round(queue_us, 1),
                                   "dur": round(qm * 1e3, 1),
                                   "cat": "queue", "args": args})
                events.append({"name": fam, "ph": "X", "pid": 1,
                               "tid": 2, "ts": round(launch_us, 1),
                               "dur": round(max(rm, 1e-3) * 1e3, 1),
                               "cat": "dispatch", "args": args})
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"records": len(recs),
                              "dispatches": self._c_disp.value,
                              "busy_ms_total": round(busy, 3),
                              "utilization": self._g_util.value}}

    # -- roll-up -------------------------------------------------------------

    def summary(self) -> dict:
        """The /debug/metrics `devprof` section."""
        self._refresh_utilization()
        with self._lock:
            n_fams = len(self._fams)
            storms = sum(f["storms"] for f in self._fams.values())
            compile_ms = sum(f["compile_ms"] for f in self._fams.values())
            ring = len(self._ring)
        return {
            "enabled": True,
            "dispatches": self._c_disp.value,
            "ring_records": ring,
            "utilization_pct": self._g_util.value,
            "queue_gap_ms": self._m.histogram(
                "dgraph_device_queue_gap_ms").snapshot(),
            "dispatch_ms": self._m.histogram(
                "dgraph_device_dispatch_ms").snapshot(),
            "compiles": self._c_compiles.value,
            "compile_ms_total": round(compile_ms, 3),
            "program_families": n_fams,
            "retrace_storms": storms,
            "hbm": self.hbm_snapshot(),
        }
