#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served path.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never imports JAX: the chip belongs to the one `serve` child,
started with JAX_PLATFORMS=tpu, so a machine without a chip is a start-up
error. Set-up (generate from --seed -> bulk, or the store cache -> copy ->
serve -> warm this cell's op shapes) is timed as setup_s; the window
drives HTTP /query; every answer is compared with the plain reference
afterwards. The last line of stdout is the result.

Everything that belongs to one configuration, traffic mix, op or metric is
a file of its own, found by the name in BENCHMARK.json: configs/<c>.json,
traffic/<mix>.json, ops/<op>.py, e2e_metrics/<m>.py, layer_metrics/<m>.py.

`--rehearsal` is the builder's CPU dry run (toy --scale, JAX_PLATFORMS=cpu):
same flow, names the CPU as its device and always says correct: false.
`--control <name>` puts the reference, with one guarantee broken, in the
program's place; it has to come out as not correct.
"""

from __future__ import annotations

T_START = __import__("time").monotonic()

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import check, loop, stats  # noqa: E402
from harness.graph import Graph  # noqa: E402
from harness.server import Server  # noqa: E402
from harness.store import cached_store, private_copy  # noqa: E402

WARM_QUIET = 4        # requests in a row of one op that load no program
WARM_MAX = 48
TRACE_AFTER_S = 2.0   # the traced interval starts this far into the window
TRACE_FOR_S = 6.0     # and lasts this long, or half the window if shorter:
#                       a busy chip runs some 300,000 instructions a second


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind}/{name}.py: no such {kind} file")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Ctx:
    """What an op's draw() and request() may read."""

    def __init__(self, g: Graph, cfg: dict):
        self.g, self.cfg = g, cfg
        self.edge_limit = int(cfg.get("edge_limit", 1 << 30))


class RunData:
    """What a metric reader may read."""

    def __init__(self) -> None:
        self.reqs: list[dict] = []
        self.t0 = self.seconds = self.setup_s = 0.0
        self.before: dict = {}
        self.after: dict = {}
        self.trace: dict | None = None
        self.trace_span: tuple[float, float] | None = None
        self.kernel_evidence: list[dict] = []
        self.device: dict = {}

    def grown(self, series: str) -> float:
        """Growth of one /metrics series over the window."""
        return self.after["prom"].get(series, 0.0) - \
            self.before["prom"].get(series, 0.0)


def cell_metrics(bench: dict, section: str, cell: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def warm(srv, ops, ctx, traffic, seed: int) -> tuple[list[dict], dict]:
    """Each op of the mix, alone and then at each concurrency the loop can
    reach (2..clients at once: the batcher stacks concurrent requests and
    each occupancy has programs of its own), until WARM_QUIET rounds of it
    in a row load no program (compile or persistent-cache hit): this
    cell's shapes and no others. Folds the tablets on the way. Returns
    (requests, rounds)."""
    import threading

    reqs, rounds = [], {}
    rng = np.random.default_rng([seed, 15485863])
    clients = int(traffic["clients"])
    levels = range(1, clients + 1)
    last = srv.compiles()["programs_loaded"]
    for name in sorted(traffic["ops"]):
        for k in levels:
            quiet = sent = 0
            while quiet < (WARM_QUIET if k == 1 else 2) and sent < WARM_MAX:
                params = [ops[name].draw(ctx, rng) for _ in range(k)]
                got: list[dict] = []
                ts = [threading.Thread(
                    target=lambda p=p: got.append(
                        loop.send(srv, ops, ctx, name, p))) for p in params]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                for r in got:
                    r["warm"] = True
                reqs.extend(got)
                sent += 1
                now = srv.compiles()["programs_loaded"]
                quiet = quiet + 1 if now == last else 0
                last = now
            rounds[name] = rounds.get(name, 0) + sent
    return reqs, rounds


def loaded_by_family(before: dict, after: dict) -> dict:
    """Program builds per family between two /debug/compiles readings."""
    fb = before["compiles"].get("families", {})
    out = {}
    for fam, row in after["compiles"].get("families", {}).items():
        d = (row.get("builds") or 0) + (row.get("compiles") or 0) \
            - (fb.get(fam, {}).get("builds") or 0) \
            - (fb.get(fam, {}).get("compiles") or 0)
        if d:
            out[fam] = {"builds+compiles": d,
                        "recent_shapes": row.get("recent_shapes", [])[-4:]}
    return out


def kernel_evidence(srv, cfg: dict, reqs: list[dict], ops, ctx,
                    per_op: int = 6) -> list[dict]:
    """For ops the config names device kernels for (one family, or a list
    where the executor may pick): did the newest few requests' traces
    hold a device_kernel span of such a family?"""
    want = cfg.get("device_kernels", {})
    top = srv.call("GET", "/debug/top?window=86400&n=4096&group=shape")
    rows = {row["key"]: row for row in top.get("top", [])}
    out = []
    for name, kernel in want.items():
        families = [kernel] if isinstance(kernel, str) else list(kernel)
        mine = [r for r in reqs if r["op"] == name and r["ok"]][-per_op:]
        for r in mine:
            _, _, body = ops[name].request(r["params"], ctx)
            row = rows.get(body[:200])
            kernels = srv.trace_kernels(row["trace_id"]) \
                if row and row.get("trace_id") else None
            if kernels is None:
                continue                      # not sampled: nothing to read
            out.append({"op": name, "want": families, "kernels": kernels,
                        "found": any(k in kernels for k in families)})
    return out


def reduce_trace(ctl: str, workdir: str) -> dict | None:
    """The trace reduction, in a process of its own (it imports jax's
    reader; this parent stays off JAX)."""
    out = os.path.join(workdir, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
         os.path.join(ctl, "trace"), out], env=env, capture_output=True,
        text=True, timeout=240)
    if res.returncode != 0:
        log("trace reduction failed:", res.stderr[-1500:])
        return None
    return load_json(out)


class Run:
    """One run of one cell: set_up, window, after_window, compare, result.
    The phases share the server child, the request logs and RunData."""

    def __init__(self, args) -> None:
        self.args = args
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"no workload {args.workload!r} in "
                             f"BENCHMARK.json (have: "
                             f"{', '.join(sorted(cells))})")
        cell = cells[args.workload]
        conf = next(c for c in self.bench["configs"]
                    if c["name"] == cell["config"])
        self.cfg = load_json(os.path.join(ROOT, conf["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", f"{cell['traffic']}.json"))
        if self.traffic.get("loop", "closed") not in loop.LOOPS:
            raise SystemExit(f"traffic {cell['traffic']}: loop kind "
                             f"{self.traffic['loop']!r} has no generator yet")
        self.ops = {n: load_module("ops", n) for n in self.traffic["ops"]}
        if args.scale and not args.rehearsal:
            raise SystemExit("--scale is for --rehearsal only")
        if args.scale:
            self.cfg["data"]["scale"] = args.scale
        self.chips = int(self.cfg.get("chips", 1))
        if self.chips != int(cell["chips"]):
            raise SystemExit(f"cell asks for {cell['chips']} chips, its "
                             f"config for {self.chips}")
        self.seed = int(args.seed)
        self.timings: dict = {}
        self.rd = RunData()
        self.rd.seconds = float(args.seconds)
        self.srv: Server | None = None
        self.workdir = tempfile.mkdtemp(prefix="dgraph-bench-")
        self.ctl = os.path.join(self.workdir, "trace_ctl")
        os.makedirs(self.ctl)
        self.problems: list[str] = []
        self.warm_reqs: list[dict] = []
        self.peak = 0

    def timed(self, key: str, fn, *a, **kw):
        t = time.monotonic()
        out = fn(*a, **kw)
        self.timings[key] = time.monotonic() - t
        return out

    # the tests put a child with the timed path broken here
    serve_wrapper: str | None = None

    def start_server(self) -> None:
        """A traced run's child goes through serve_traced.py and samples
        every request's spans."""
        args = self.args
        serve_args = list(self.cfg.get("serve_args", []))
        wrapper, env = self.serve_wrapper, {}
        if args.trace:
            wrapper = wrapper or os.path.join(HERE, "serve_traced.py")
            env["BENCH_TRACE_CTL"] = self.ctl
            serve_args += ["--span_sample", "1.0"]
        self.srv = Server(
            ROOT, self.postings, os.path.join(self.workdir, "serve.log"),
            serve_args, "cpu" if args.rehearsal else "tpu", self.chips,
            wrapper, env)

    def set_up(self) -> None:
        args, cfg = self.args, self.cfg
        self.g = self.timed("generate_s", Graph.from_config, cfg["data"],
                            self.seed)
        tag = cfg["name"] + (f"-s{args.scale}" if args.scale else "")
        store = cached_store(ROOT, cfg, self.seed, self.g, self.timings,
                             name=tag)
        self.postings = self.timed("copy_s", private_copy, store,
                                   self.workdir)
        self.timed("serve_start_s", self.start_server)
        rt = self.srv.call("GET", "/debug/compiles")["runtime"]
        self.rd.device = {"platform": rt["platform"],
                          "kind": rt["device_kind"],
                          "count": rt["device_count"]}
        if not args.rehearsal and (rt["platform"] != "tpu"
                                   or rt["device_count"] < self.chips
                                   or rt.get("pallas_interpret")):
            raise SystemExit(
                f"serve came up on {rt['platform']} x{rt['device_count']} "
                f"(interpret={rt.get('pallas_interpret')}); the cell needs "
                f"{self.chips} TPU chip(s)")
        self.ctx = Ctx(self.g, cfg)
        self.warm_reqs, self.timings["warm_requests"] = self.timed(
            "warm_s", warm, self.srv, self.ops, self.ctx, self.traffic,
            self.seed)
        self.rd.before = self.srv.counters()

    def trace_part_of_window(self) -> None:
        """The traced run's side thread: flag files for serve_traced.py."""
        ctl, seconds = self.ctl, self.rd.seconds
        time.sleep(min(TRACE_AFTER_S, seconds / 4))
        open(os.path.join(ctl, "start"), "w").close()
        while not os.path.exists(os.path.join(ctl, "started")):
            time.sleep(0.02)
        time.sleep(min(TRACE_FOR_S, seconds / 2))
        open(os.path.join(ctl, "stop"), "w").close()

    def window(self) -> None:
        import threading

        rd, srv, ctl = self.rd, self.srv, self.ctl

        def on_start(t0: float) -> None:
            rd.setup_s = t0 - T_START

        tracer = None
        if self.args.trace:
            tracer = threading.Thread(target=self.trace_part_of_window,
                                      daemon=True)
            tracer.start()
        rd.reqs, rd.t0 = loop.run_closed(srv, self.traffic, self.ops,
                                         self.ctx, self.seed, rd.seconds,
                                         on_start)
        rd.after = srv.counters()
        loaded = rd.after["programs_loaded"] - rd.before["programs_loaded"]
        self.timings["programs_loaded_in_window"] = loaded
        if loaded:
            log("programs loaded inside the window:", loaded,
                json.dumps(loaded_by_family(rd.before, rd.after))[:1500])
        if tracer is not None:
            tracer.join(timeout=120)
            deadline = time.monotonic() + 180
            while not os.path.exists(os.path.join(ctl, "done")) \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            if os.path.exists(os.path.join(ctl, "done")):
                rd.trace_span = (load_json(os.path.join(ctl, "started"))["t"],
                                 load_json(os.path.join(ctl, "done"))["t"])
            else:
                self.problems.append("the traced child never finished its "
                                     "trace")
            rd.kernel_evidence = kernel_evidence(srv, self.cfg, rd.reqs,
                                                 self.ops, self.ctx)
        comp = rd.after["compiles"]
        self.peak = max(d["peak_bytes_in_use"]
                        for d in comp["runtime"]["devices"])
        log("device program families holding programs:", json.dumps(
            {k: v for k, v in comp.get("cache_sizes", {}).items() if v}))

    def after_window(self) -> None:
        """Stop the server; then, with the chip free, reduce the trace."""
        problem = self.srv.stop()
        self.srv = None
        if problem:
            self.problems.append(problem)
        rd = self.rd
        if rd.trace_span is not None:
            rd.trace = reduce_trace(self.ctl, self.workdir)
        if rd.trace is not None:
            rd.trace["window_s"] = rd.trace_span[1] - rd.trace_span[0]

    def compare(self) -> None:
        control = self.args.control
        control_g = self.g.without_edges(
            check.APPROX_DROP, np.random.default_rng([self.seed, 31])) \
            if control == "approx" else None
        reqs = self.rd.reqs
        picked = set(check.draw_sample(len(reqs), self.seed))
        check.envelopes_only([r for i, r in enumerate(reqs)
                              if i not in picked])
        self.problems += self.timed(
            "reference_s", check.check_requests, self.g,
            self.warm_reqs + [reqs[i] for i in sorted(picked)],
            self.ops, control, control_g)
        self.every = self.warm_reqs + reqs
        self.timings["compared_of_window"] = len(picked)

    def clean_up(self) -> None:
        if self.srv is not None:
            log("serve log tail:\n" + self.srv.log_tail(3000))
            self.srv.kill()
        shutil.rmtree(self.workdir, ignore_errors=True)

    def result(self) -> dict:
        args, rd = self.args, self.rd
        attempted, failed = stats.counts(rd.reqs)
        numbers = {
            "wrong_or_failed_in_window": (failed, 0),
            "wrong_or_failed_in_warmup": (stats.counts(self.warm_reqs)[1],
                                          0),
            "unanswered": (sum(1 for r in self.every if not r["ok"]), 0),
        }
        checks_passed = all(v <= lim for v, lim in numbers.values()) \
            and not self.problems and attempted > 0
        section, kind = ("per_layer", "layer_metrics") if args.trace \
            else ("end_to_end", "e2e_metrics")
        metrics = {}
        for m in cell_metrics(self.bench, section, args.workload):
            value = load_module(kind, m["name"]).read(rd)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {**rd.device, "memory_peak_bytes": self.peak}
        # a CPU run can never be taken for a measurement: `correct` is held
        # false, and the comparison's own verdict goes beside it
        out = {"correct": checks_passed and not args.rehearsal,
               "attempted": attempted, "failed": failed, "metrics": metrics,
               "device": device}
        if args.trace and rd.trace and rd.trace.get("busy_s"):
            device["busy_s"] = rd.trace["busy_s"]
            device["window_s"] = rd.trace["window_s"]
            out["breakdown"] = {
                "device_ops": rd.trace.get("device_ops", [])[:10],
                "idle_gaps": rd.trace.get("idle_gaps", [])[:10]}
        if args.rehearsal:
            out["rehearsal"], out["checks_passed"] = True, checks_passed
        if args.control:
            out["control"] = args.control
        by_op: dict[str, int] = {}
        for r in rd.reqs:
            by_op[r["op"]] = by_op.get(r["op"], 0) + 1
        out["info"] = {
            "workload": args.workload, "seed": self.seed,
            "seconds": rd.seconds, "by_op": by_op,
            "timings": {k: (round(v, 3) if isinstance(v, float) else v)
                        for k, v in self.timings.items()}}
        out["compared"] = {k: {"value": v, "limit": lim}
                           for k, (v, lim) in numbers.items()}
        for p in self.problems[:12]:
            log("problem:", p)
        if rd.kernel_evidence:
            log("device_kernel evidence:",
                json.dumps(rd.kernel_evidence[:4]))
        log("compared:", " ".join(f"{k}={v} (limit {lim})"
                                  for k, (v, lim) in numbers.items()))
        return out


def run(args) -> int:
    r = Run(args)
    try:
        r.set_up()
        r.window()
        r.after_window()
        r.compare()
    finally:
        r.clean_up()
    print(json.dumps(r.result()), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dry run; always says correct: false")
    ap.add_argument("--scale", type=int, default=0,
                    help="rehearsal only: the graph's scale")
    ap.add_argument("--control", choices=sorted(check.CONTROLS),
                    help="answer from the reference with one guarantee "
                         "broken; must come out as not correct")
    args = ap.parse_args(argv)
    # a terminated run still stops its serve child on the way out
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        return run(args)
    except SystemExit as e:     # a refusal with its reason: no result line
        if isinstance(e.code, str):
            log(e.code)
            return 2
        raise
    except Exception:  # noqa: BLE001 — top boundary: no result line, exit 1
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
