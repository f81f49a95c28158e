#!/usr/bin/env python3
"""chip_smoke.py — the served path on the chip, end to end, once.

    python3 chip_smoke.py            # one TPU chip (or one host's chips)
    python3 chip_smoke.py --mesh     # four chips: the child is `serve --mesh`

Stands up the deployment a user would: generates an R-MAT scale-20 x 16
`follows` graph (16.08M distinct edges, 547k subjects) with an indexed int
value and a low-cardinality key on every subject, loads it with
`python -m dgraph_tpu bulk`, serves it with `python -m dgraph_tpu serve`,
and speaks DQL text in / JSON out over HTTP. This parent never imports
JAX: the chip belongs to the one `serve` child, started with
JAX_PLATFORMS=tpu so a machine without a chip is a start-up error, not
XLA:CPU. The graph is above every host/device crossover, so each device
tier engages by default — no module global, forcing flag or monkeypatch.

Every answer is compared with a plain numpy reference written here
(independent of dgraph_tpu.query / dgraph_tpu.ops), and the server's own
surfaces must show that the device did the work (/debug/compiles,
/debug/top, /debug/traces, /debug/metrics, /metrics). Then one
acknowledged write is read back, the server is stopped and restarted on
the same postings dir, the write is read back again (WAL replay), and a
battery query re-run must find its programs in the persistent compile
cache.

One JSON object per phase on stdout, then a summary ending in
"claim": null, then — only if every phase and check passed on a TPU — the
pass line {"ok": true, "device": {...}} as the last line, exit 0. All
times printed are set-up / smoke timings on the host clock, never a
metric. `--rehearsal` is the builder's CPU dry run at toy scale: same
phases and comparisons, no device evidence required, prints
"rehearsal": true and can never print the pass line.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request
from dataclasses import dataclass, field

import numpy as np

from dgraph_tpu.models.rmat import rmat_csr
from dgraph_tpu.storage import native

HERE = os.path.dirname(os.path.abspath(__file__))
DUMP_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

SCHEMA = ("follows: [uid] .\n"
          "score: int @index(int) .\n"
          "grp: int @index(int) .\n")
SCORE_MOD = 24      # all-int values whose total stays < 2**24: the device
#                     segmented reduce is exact in f32 and therefore taken
GRP_MOD = 64        # low-cardinality groupby key
RECURSE_SEEDS = 128
RECURSE_DEPTH = 3
SHORTEST_PAIRS = 10

HOP_GROUPS = 8      # one-hop root: 8 of the 64 grp values, a ~68k-subject
#                     frontier whose expand the planner estimates above its
#                     device floor. No crossover value is copied here: a
#                     query that stayed on the host shows no device_kernel
#                     span of its tier, and that is what fails the run


# rmat20 x 16 runs inside the contract's time limit (~420 s on the chip
# machine), so nothing is cut; a cut of scale would be named here
SCALE_CUT = None


# every phase of a complete run, in order; a run that ends without one of
# them did not reach its end, whatever else it reports
PHASES = ("generate", "bulk", "serve_cold", "battery", "device_evidence",
          "write_readback", "stop", "restart", "restart_readback",
          "restart_battery", "stop_restart")


@dataclass
class Config:
    scale: int = 20
    edge_factor: int = 16
    seed: int = 20260926
    mesh: bool = False
    rehearsal: bool = False


@dataclass
class Run:
    cfg: Config
    workdir: str
    failures: list[str] = field(default_factory=list)
    timings: dict = field(default_factory=dict)
    summary: dict = field(default_factory=dict)
    procs: list = field(default_factory=list)

    def fail(self, msg: str) -> None:
        self.failures.append(msg)
        emit({"check_failed": msg})

    def require(self, ok: bool, msg: str) -> None:
        if not ok:
            self.fail(msg)


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


class Phase:
    """`with Phase(run, "bulk") as ph:` — times the block on the host clock
    and prints one JSON object; ph[...] adds fields. An exception leaves
    the phase marked failed and propagates: nothing turns a failed phase
    into a field of a passing run."""

    def __init__(self, run: Run, name: str) -> None:
        self.run, self.name, self.fields = run, name, {}

    def __setitem__(self, k, v) -> None:
        self.fields[k] = v

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        dt = round(time.perf_counter() - self.t0, 3)
        self.run.timings[self.name] = dt
        rec = {"phase": self.name, "seconds": dt, **self.fields}
        if et is not None:
            rec["failed"] = f"{et.__name__}: {ev}"
            self.run.failures.append(f"phase {self.name}: {et.__name__}")
        emit(rec)
        return False


# -- the graph and its plain numpy reference ---------------------------------

class Graph:
    """Forward CSR in uid space plus the per-subject value columns."""

    def __init__(self, subjects, indptr, indices, seed: int) -> None:
        self.subjects = np.asarray(subjects, dtype=np.int64)
        self.indptr = np.asarray(indptr, dtype=np.int64)
        self.indices = np.asarray(indices, dtype=np.int64)
        self.n = int(max(self.subjects.max(), self.indices.max())) + 1
        self.row = np.full(self.n, -1, dtype=np.int64)
        self.row[self.subjects] = np.arange(len(self.subjects))
        rng = np.random.default_rng(seed)
        self.score = np.full(self.n, -1, dtype=np.int64)
        self.score[self.subjects] = rng.integers(
            0, SCORE_MOD, len(self.subjects))
        self.grp = np.full(self.n, -1, dtype=np.int64)
        self.grp[self.subjects] = rng.integers(
            0, GRP_MOD, len(self.subjects))

    @property
    def degree(self) -> np.ndarray:
        d = np.zeros(self.n, dtype=np.int64)
        d[self.subjects] = np.diff(self.indptr)
        return d

    def edge_positions(self, frontier: np.ndarray):
        """(pos, offs): positions into `indices` of every out-edge of the
        frontier, and per-frontier-node offsets into pos."""
        rows = self.row[frontier]
        ok = rows >= 0
        rc = np.where(ok, rows, 0)
        starts = np.where(ok, self.indptr[rc], 0)
        counts = np.where(ok, self.indptr[rc + 1] - starts, 0)
        offs = np.zeros(len(frontier) + 1, dtype=np.int64)
        np.cumsum(counts, out=offs[1:])
        pos = np.repeat(starts - offs[:-1], counts) + np.arange(offs[-1])
        return pos, offs

    def has_edge(self, u: int, t: int) -> bool:
        r = self.row[u] if u < self.n else -1
        if r < 0:
            return False
        row = self.indices[self.indptr[r]: self.indptr[r + 1]]
        j = int(np.searchsorted(row, t))
        return j < len(row) and row[j] == t

    def with_edge(self, u: int, t: int) -> "Graph":
        """A copy holding one more edge u -> t (u an existing subject)."""
        r = int(self.row[u])
        lo, hi = int(self.indptr[r]), int(self.indptr[r + 1])
        at = lo + int(np.searchsorted(self.indices[lo:hi], t))
        g = object.__new__(Graph)
        g.__dict__.update(self.__dict__)
        g.indices = np.insert(self.indices, at, t)
        g.indptr = self.indptr.copy()
        g.indptr[r + 1:] += 1
        return g


def ref_onehop(g: Graph, lo: int, sval: int) -> list[int]:
    """uids reached over one `follows` hop from every subject with
    lo <= grp < lo + HOP_GROUPS, kept where score == sval."""
    frontier = np.flatnonzero((g.grp >= lo) & (g.grp < lo + HOP_GROUPS))
    pos, _ = g.edge_positions(frontier)
    dest = np.unique(g.indices[pos])
    return dest[g.score[dest] == sval].tolist()


def ref_chain(g: Graph, gval: int, sval: int) -> list[int]:
    """As ref_onehop, over two `follows` hops."""
    frontier = np.flatnonzero(g.grp == gval)
    for _ in range(2):
        pos, _offs = g.edge_positions(frontier)
        frontier = np.unique(g.indices[pos])
    return frontier[g.score[frontier] == sval].tolist()


def ref_recurse(g: Graph, seeds: np.ndarray, depth: int):
    """@recurse(depth) { follows } as the nested JSON the server renders.

    Level-set expansion with EDGE dedup (reference query/recurse.go): an
    edge is traversed the first time its source is in a frontier and never
    again; a node reached over a fresh edge re-enters the next frontier
    even if it was seen before. Rendering follows the reference's JSON
    pruning: targets of the last executed hop are bare {"uid"} objects,
    and above it an object with nothing to show is dropped from its
    parent's list. Returns (json_rows, traversed_edges)."""
    seen = np.zeros(len(g.indices), dtype=bool)
    frontier = np.unique(seeds)
    levels = []                       # per level: {uid: fresh targets}
    traversed = 0
    for _ in range(depth):
        pos, offs = g.edge_positions(frontier)
        traversed += len(pos)
        fresh = ~seen[pos]
        seen[pos] = True
        kids = {}
        for i, u in enumerate(frontier.tolist()):
            sl = slice(offs[i], offs[i + 1])
            kids[u] = g.indices[pos[sl]][fresh[sl]]
        levels.append(kids)
        nxt = g.indices[pos][fresh]
        frontier = np.unique(nxt)
        if not len(frontier):
            break

    def node(u: int, lvl: int):
        kids = levels[lvl][u].tolist()
        if lvl + 1 == len(levels):
            shown = [{"uid": hex(t)} for t in kids]
        else:
            shown = [o for o in (node(t, lvl + 1) for t in kids) if o]
        return {"follows": shown} if shown else None

    if not levels:
        return [], 0
    rows = [o for o in (node(s, 0) for s in np.unique(seeds).tolist()) if o]
    return rows, traversed


def ref_bfs_dist(g: Graph, src: int, dst: int, max_depth: int = 64):
    """Hop distance src -> dst by level-set BFS, None when unreachable."""
    if src == dst:
        return 0
    visited = np.zeros(g.n, dtype=bool)
    visited[src] = True
    frontier = np.asarray([src], dtype=np.int64)
    for d in range(1, max_depth + 1):
        pos, _ = g.edge_positions(frontier)
        reached = np.zeros(g.n, dtype=bool)
        reached[g.indices[pos]] = True
        reached &= ~visited
        if reached[dst]:
            return d
        frontier = np.flatnonzero(reached)
        if not len(frontier):
            return None
        visited |= reached
    return None


def ref_groupby(g: Graph) -> dict[int, tuple[int, float]]:
    """{grp: (count, mean score)} over every subject."""
    k = g.grp[g.subjects]
    cnt = np.bincount(k, minlength=GRP_MOD)
    tot = np.bincount(k, weights=g.score[g.subjects].astype(np.float64),
                      minlength=GRP_MOD)
    return {int(i): (int(cnt[i]), float(tot[i] / cnt[i]))
            for i in range(GRP_MOD) if cnt[i]}


def pick_write_edge(g: Graph, seeds: np.ndarray, n_shards: int):
    """The one edge the smoke commits: out of a recurse seed (so the
    re-run after the restart must render it) into a light node, chosen so
    that no compiled program's SHAPE moves — the restart is then a clean
    test of the persistent compile cache. The source is an existing
    subject and the target an existing destination (rank spaces keep their
    sizes), the edge count stays inside its 8192-edge padding block, and
    under row-range sharding the source sits outside the heaviest shard
    (whose edge count is every shard's padded capacity)."""
    rows_per = -(-len(g.subjects) // n_shards)
    bounds = g.indptr[np.minimum(np.arange(n_shards + 1) * rows_per,
                                 len(g.subjects))]
    heavy = int(np.argmax(np.diff(bounds))) if n_shards > 1 else -1
    if len(g.indices) % 8192 == 0:
        raise RuntimeError("edge count sits on a padding-block boundary: "
                           "one more edge would move a program's shape")
    s0 = next((int(s) for s in seeds[::-1].tolist()
               if g.row[s] // rows_per != heavy), None)
    if s0 is None:
        raise RuntimeError("every recurse seed sits in the heaviest shard")
    is_dest = np.zeros(g.n, dtype=bool)
    is_dest[g.indices] = True
    light = g.subjects[(g.degree[g.subjects] <= 2) & is_dest[g.subjects]]
    t0 = next((int(t) for t in light.tolist()
               if t != s0 and not g.has_edge(s0, t)), None)
    if t0 is None:
        raise RuntimeError(f"no light target for a new edge out of "
                           f"{s0:#x}")
    return s0, t0


def pick_recurse_seeds(g: Graph, rng, n: int, depth: int) -> np.ndarray:
    """n seeded subjects whose depth-hop walk count is small, so the nested
    JSON stays bounded (the kernel still streams all E edges per level,
    whatever the frontier). walks[u] bounds the rows rendered under u."""
    src = np.repeat(g.subjects, np.diff(g.indptr))
    walks = g.degree.astype(np.float64)
    for _ in range(depth - 1):
        walks = np.bincount(src, weights=walks[g.indices], minlength=g.n)
    cand = g.subjects[(walks[g.subjects] >= 16)
                      & (walks[g.subjects] <= 4096)]
    if len(cand) < n:               # toy graphs: take the lightest n
        order = np.argsort(walks[g.subjects], kind="stable")
        cand = g.subjects[order[: max(n, 1)]]
    return np.sort(rng.choice(cand, size=min(n, len(cand)), replace=False))


# -- set-up: RDF + schema (numpy only) ----------------------------------------

_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)


def _hex_cols(vals: np.ndarray, width: int) -> np.ndarray:
    """[n, width] uint8 zero-padded lowercase hex digits."""
    shifts = np.arange(width - 1, -1, -1, dtype=np.int64) * 4
    return _HEX[(vals[:, None] >> shifts[None, :]) & 0xF]


def write_rdf(g: Graph, path: str) -> int:
    """N-Quads for the whole graph; the 16M uid edges are rendered as one
    fixed-width byte matrix per chunk, no per-edge Python."""
    width = max(5, (int(g.n).bit_length() + 3) // 4)
    tmpl = np.frombuffer(
        (b"<0x" + b"0" * width + b"> <follows> <0x" + b"0" * width
         + b"> .\n"), dtype=np.uint8)
    s_at, o_at = 3, 3 + width + len(b"> <follows> <0x")
    src = np.repeat(g.subjects, np.diff(g.indptr))
    quads = 0
    with open(path, "wb") as f:
        for lo in range(0, len(src), 1 << 20):
            hi = min(lo + (1 << 20), len(src))
            buf = np.tile(tmpl, (hi - lo, 1))
            buf[:, s_at: s_at + width] = _hex_cols(src[lo:hi], width)
            buf[:, o_at: o_at + width] = _hex_cols(g.indices[lo:hi], width)
            f.write(buf.tobytes())
            quads += hi - lo
        lines = []
        for u, sc, gr in zip(g.subjects.tolist(),
                             g.score[g.subjects].tolist(),
                             g.grp[g.subjects].tolist()):
            lines.append(f'<0x{u:x}> <score> "{sc}"^^<xs:int> .\n'
                         f'<0x{u:x}> <grp> "{gr}"^^<xs:int> .\n')
        f.write("".join(lines).encode())
        quads += 2 * len(g.subjects)
    return quads


# -- the serve child ----------------------------------------------------------

class Server:
    """One `python -m dgraph_tpu serve` child — the only process that may
    touch JAX. Output goes to a log file the banner is parsed from."""

    def __init__(self, run: Run, postings: str, tag: str) -> None:
        self.run, self.tag = run, tag
        self.log_path = os.path.join(run.workdir, f"serve_{tag}.log")
        cfg = run.cfg
        env = dict(os.environ)
        env["PYTHONUNBUFFERED"] = "1"
        env["JAX_PLATFORMS"] = "cpu" if cfg.rehearsal else "tpu"
        if cfg.rehearsal and cfg.mesh:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                "host_platform_device_count=4").strip()
        args = [sys.executable, "-m", "dgraph_tpu", "serve", "-p", postings,
                "--port", "0", "--grpc_port", "0", "--span_sample", "1.0"]
        if cfg.mesh:
            args.append("--mesh")
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(args, cwd=HERE, env=env,
                                     stdout=self.log,
                                     stderr=subprocess.STDOUT)
        run.procs.append(self.proc)
        self.banner, self.port = self._wait_banner()

    def _wait_banner(self, timeout: float = 600.0) -> tuple[str, int]:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with open(self.log_path, "rb") as f:
                text = f.read().decode("utf-8", "replace")
            m = re.search(r"^.*serving HTTP on [\w.]+:(\d+).*$", text, re.M)
            if m:
                return m.group(0), int(m.group(1))
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"serve ({self.tag}) exited {self.proc.returncode} "
                    f"before its banner:\n{text[-2000:]}")
            time.sleep(0.25)
        raise RuntimeError(f"serve ({self.tag}) printed no banner in "
                           f"{timeout:.0f}s")

    def call(self, method: str, path: str, body: str | None = None,
             timeout: float = 900.0, raw: bool = False):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}",
            data=body.encode() if body is not None else None, method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                data = r.read()
        except urllib.error.HTTPError as e:
            raise RuntimeError(f"{method} {path.split('?')[0]} -> HTTP "
                               f"{e.code}: {e.read()[:600]!r}") from e
        return data.decode() if raw else json.loads(data)

    def query(self, q: str) -> tuple[dict, float]:
        # the reference's default budget is 1e6 traversed edges per query;
        # a BFS over this graph walks more, so raise it per request
        t0 = time.perf_counter()
        out = self.call("POST", f"/query?edgeLimit={1 << 30}", q)
        dt = time.perf_counter() - t0
        if "data" not in out:
            raise RuntimeError(f"query failed: {json.dumps(out)[:400]}")
        return out["data"], dt

    def metrics(self) -> dict[str, float]:
        """/metrics as {series: value}; labelled series keep their braces."""
        out = {}
        for line in self.call("GET", "/metrics", raw=True).splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    def cost_row(self, q: str) -> dict | None:
        top = self.call("GET", "/debug/top?window=86400&n=500&group=shape")
        for row in top.get("top", []):
            if row.get("key") == q[:200]:
                return row
        return None

    def trace_kernels(self, trace_id: str) -> list[str]:
        """kernel= attrs of every device_kernel span of one trace."""
        tree = self.call("GET", f"/debug/traces/{trace_id}?view=tree")
        found = []

        def walk(n):
            if n.get("name") == "device_kernel":
                found.append(str(n.get("attrs", {}).get("kernel", "")))
            for c in n.get("children", ()):
                walk(c)

        for n in tree.get("tree", ()):
            walk(n)
        return found

    def stop(self) -> None:
        """Clean shutdown over /admin/shutdown. A server that has to be
        signalled instead is a failed run: the restart phases would be
        reading a WAL the server never closed."""
        if self.proc.poll() is None:
            try:
                self.call("POST", "/admin/shutdown", "", timeout=30)
            except OSError:         # the listener may close mid-reply
                pass
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.run.fail(f"serve ({self.tag}) did not exit within 120s "
                              f"of /admin/shutdown; terminated")
                self.proc.terminate()
                try:
                    self.proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    self.proc.kill()
                    self.proc.wait(timeout=30)
            self.run.require(self.proc.returncode == 0,
                             f"serve ({self.tag}) exited "
                             f"{self.proc.returncode}")
        self.log.close()


# -- device evidence ----------------------------------------------------------

def mesh_counters(m: dict[str, float]) -> dict:
    return {"dispatches": m.get("dgraph_mesh_dispatches_total", 0.0),
            "fallbacks": sum(v for k, v in m.items()
                             if k.startswith("dgraph_mesh_fallbacks_total"))}


def check_query_evidence(run: Run, srv: Server, name: str, q: str,
                         kernel: str, need_bytes: bool) -> dict:
    """The server's own account of one battery query: its /debug/top cost
    record and the device_kernel spans of its trace. Enforced on the chip,
    reported in a rehearsal (toy graphs sit below every crossover)."""
    row = srv.cost_row(q) or {}
    kernels = srv.trace_kernels(row["trace_id"]) if row.get("trace_id") \
        else []
    ev = {"device_ms": row.get("device_ms"), "bytes": row.get("bytes"),
          "compile_ms": row.get("compile_ms"), "edges": row.get("edges"),
          "device_kernel_spans": kernels}
    if not run.cfg.rehearsal:
        run.require(bool(row), f"{name}: no /debug/top record")
        run.require((row.get("device_ms") or 0) > 0,
                    f"{name}: /debug/top device_ms is not > 0")
        if need_bytes:
            run.require((row.get("bytes") or 0) > 0,
                        f"{name}: /debug/top transfer bytes are not > 0")
        run.require(kernel in kernels,
                    f"{name}: no device_kernel span kernel={kernel} "
                    f"(got {kernels})")
    return ev


def run_query(run: Run, srv: Server, name: str, q: str, kernel: str,
              check, need_bytes: bool = True) -> dict:
    """POST one battery query, compare its answer, collect its evidence."""
    before = mesh_counters(srv.metrics()) if run.cfg.mesh else None
    data, dt = srv.query(q)
    problem = check(data)
    run.require(problem is None, f"{name}: answer != numpy reference "
                                 f"({problem})")
    ev = check_query_evidence(run, srv, name, q, kernel, need_bytes)
    rec = {"query": name, "seconds": round(dt, 3), "correct": problem is None,
           **ev}
    if before is not None:
        after = mesh_counters(srv.metrics())
        rec["mesh_dispatches"] = after["dispatches"] - before["dispatches"]
        rec["mesh_fallbacks"] = after["fallbacks"] - before["fallbacks"]
    return rec


# -- battery ------------------------------------------------------------------

def uid_list(uids) -> str:
    return ", ".join(hex(int(u)) for u in uids)


def q_onehop(tag: str, lo: int, sval: int) -> str:
    # a frontier of ~68k subjects: the planner must estimate the expand
    # above its own device floor, or it stays a host gather
    root = f"ge(grp, {lo})" if lo else f"lt(grp, {HOP_GROUPS})"
    return (f"{{ var(func: {root}) {{ {tag} as follows "
            f"@filter(eq(score, {sval})) }} "
            f"{tag}_out(func: uid({tag})) {{ uid }} }}")


def q_chain(tag: str, gval: int, sval: int) -> str:
    return (f"{{ var(func: eq(grp, {gval})) {{ follows {{ {tag} as follows "
            f"@filter(eq(score, {sval})) }} }} "
            f"{tag}_out(func: uid({tag})) {{ uid }} }}")


def q_recurse(tag: str, seeds) -> str:
    return (f"{{ {tag}(func: uid({uid_list(seeds)})) "
            f"@recurse(depth: {RECURSE_DEPTH}) {{ follows }} }}")


def q_shortest(tag: str, src: int, dst: int) -> str:
    return (f"{{ {tag} as shortest(from: {hex(src)}, to: {hex(dst)}) "
            f"{{ follows }} {tag}_out(func: uid({tag})) {{ uid }} }}")


def q_groupby(tag: str) -> str:
    return (f"{{ var(func: has(score)) {{ {tag}_v as score }} "
            f"{tag}(func: has(score)) @groupby(grp) "
            f"{{ count(uid) {tag}_avg: avg(val({tag}_v)) }} }}")


def check_uids(tag: str, want: list[int]):

    def check(data):
        got = [int(r["uid"], 16) for r in data.get(f"{tag}_out", [])]
        if got != want:
            return f"{len(got)} uids vs {len(want)} expected"
        return None
    return check


def check_recurse(g: Graph, tag: str, seeds):
    want, traversed = ref_recurse(g, np.asarray(seeds), RECURSE_DEPTH)

    def check(data):
        got = data.get(tag, [])
        if got != want:
            return (f"nested result differs ({len(json.dumps(got))} vs "
                    f"{len(json.dumps(want))} JSON bytes)")
        return None
    return check, traversed


def check_shortest(g: Graph, tag: str, src: int, dst: int):
    want = ref_bfs_dist(g, src, dst)

    def check(data):
        paths = data.get("_path_", [])
        if want is None:
            return None if not paths else "path returned, none expected"
        if len(paths) != 1:
            return f"{len(paths)} paths, 1 expected"
        node, path = paths[0], []
        weight = node.get("_weight_")
        while True:
            path.append(int(node["uid"], 16))
            nxt = node.get("follows")
            if not nxt:
                break
            node = nxt[0]
        if path[0] != src or path[-1] != dst:
            return f"endpoints {path[0]:#x}..{path[-1]:#x}"
        if len(path) - 1 != want or weight != float(want):
            return f"length {len(path) - 1} vs BFS distance {want}"
        for u, t in zip(path, path[1:]):
            if not g.has_edge(u, t):
                return f"{u:#x}->{t:#x} is not an edge"
        uids = sorted(int(r["uid"], 16) for r in data.get(f"{tag}_out", []))
        if uids != sorted(set(path)):
            return "uid(var) block differs from the path"
        return None
    return check, want


def check_groupby(g: Graph, tag: str):
    want = ref_groupby(g)

    def check(data):
        blocks = data.get(tag, [])
        rows = blocks[0].get("@groupby", []) if blocks else []
        got = {int(r["grp"]): (int(r["count"]), float(r[f"{tag}_avg"]))
               for r in rows}
        if set(got) != set(want):
            return f"{len(got)} groups vs {len(want)}"
        for k, (c, a) in want.items():
            gc, ga = got[k]
            # the device reduce is exact in f32 (all-int, |sum| < 2**24)
            # and avg is finalised in f64 on the host: equal to ~1 ulp
            if gc != c or abs(ga - a) > 1e-12 * max(1.0, abs(a)):
                return f"group {k}: ({gc}, {ga}) vs ({c}, {a})"
        return None
    return check, len(want)


def battery(run: Run, srv: Server, g: Graph, rng) -> dict:
    """Configs 2, 3b, 4, 5 of BASELINE.json over HTTP, plus a two-hop chain
    (the shape `serve --mesh` fuses into one mesh.plan dispatch). Each
    shape runs twice with different parameters: the first pays compile +
    fold + pull-graph prep + upload, the second is warm but can hit no
    result cache."""
    cfg = run.cfg
    mesh = cfg.mesh
    plan = {"records": []}
    recs = plan["records"]

    gvals = rng.choice(GRP_MOD, size=2, replace=False).tolist()
    svals = rng.choice(SCORE_MOD, size=2, replace=False).tolist()
    for i, (lo, sv) in enumerate(zip((0, GRP_MOD - HOP_GROUPS), svals)):
        tag = f"hop{i}"
        deg_sum = int(g.degree[(g.grp >= lo)
                               & (g.grp < lo + HOP_GROUPS)].sum())
        want = ref_onehop(g, lo, sv)
        rec = run_query(run, srv, tag, q_onehop(tag, lo, sv),
                        "dist.expand" if mesh else "csr.expand",
                        check_uids(tag, want), need_bytes=not mesh)
        recs.append({**rec, "frontier_degree_sum": deg_sum,
                     "result_uids": len(want)})

    for i, (gv, sv) in enumerate(zip(gvals, svals)):
        tag = f"chain{i}"
        want = ref_chain(g, gv, sv)
        rec = run_query(run, srv, tag, q_chain(tag, gv, sv),
                        "mesh.plan" if mesh else "csr.expand",
                        check_uids(tag, want), need_bytes=not mesh)
        recs.append({**rec, "result_uids": len(want)})

    seeds_all = pick_recurse_seeds(g, rng, 2 * RECURSE_SEEDS, RECURSE_DEPTH)
    half = len(seeds_all) // 2
    plan["recurse_seeds"] = [seeds_all[:half], seeds_all[half:]]
    for i, seeds in enumerate(plan["recurse_seeds"]):
        tag = f"rec{i}"
        check, trav = check_recurse(g, tag, seeds)
        rec = run_query(run, srv, tag, q_recurse(tag, seeds),
                        "mesh.recurse" if mesh else "pb.recurse_fused",
                        check, need_bytes=not mesh)
        recs.append({**rec, "seeds": len(seeds), "ref_traversed": trav})

    dsts = np.unique(g.indices)
    for i in range(SHORTEST_PAIRS):
        tag = f"sp{i}"
        src = int(rng.choice(g.subjects))
        dst = int(rng.choice(dsts))
        check, dist = check_shortest(g, tag, src, dst)
        rec = run_query(run, srv, tag, q_shortest(tag, src, dst),
                        "mesh.bfs" if mesh else "pb.bfs_dist", check,
                        need_bytes=False)
        recs.append({**rec, "ref_distance": dist})

    for i in range(2):
        tag = f"gb{i}"
        check, n = check_groupby(g, tag)
        rec = run_query(run, srv, tag, q_groupby(tag),
                        "segments.lens_reduce", check)
        recs.append({**rec, "groups": n})
    return plan


# -- server-wide evidence -----------------------------------------------------

def check_device_evidence(run: Run, srv: Server, recs: list[dict],
                          edges: int) -> dict:
    cfg = run.cfg
    comp = srv.call("GET", "/debug/compiles")
    rt = comp["runtime"]
    dbg = srv.call("GET", "/debug/metrics")
    prof = dbg.get("devprof", {})
    m = srv.metrics()
    fams = comp.get("families", {})
    caches = comp.get("cache_sizes", {})
    want_fams = (["dist.expand", "mesh.plan", "mesh.recurse", "mesh.bfs",
                  "segments.lens_reduce"] if cfg.mesh else
                 ["csr.expand", "pb.recurse_fused", "pb.bfs_dist",
                  "segments.lens_reduce"])
    ev = {
        "platform": rt["platform"], "device_kind": rt["device_kind"],
        "device_count": rt["device_count"],
        "default_backend": rt["default_backend"],
        "pallas_interpret": rt["pallas_interpret"],
        "families": {f: {"builds": fams.get(f, {}).get("builds"),
                         "compiles": fams.get(f, {}).get("compiles"),
                         "compile_ms": fams.get(f, {}).get("compile_ms")}
                     for f in want_fams},
        "compile_ms_total": comp.get("compile_ms_total"),
        "persistent_cache": comp.get("persistent_cache"),
        "jit_cache_sizes": {k: v for k, v in caches.items() if v},
        "hbm": prof.get("hbm"),
        "devices": rt["devices"],
        "residency_upload_failures":
            m.get("dgraph_residency_upload_failures_total", 0.0),
    }
    if cfg.mesh:
        ev["mesh"] = {k: m.get(k) for k in (
            "dgraph_mesh_devices", "dgraph_mesh_sharded_tablets",
            "dgraph_mesh_dispatches_total")}
        ev["mesh"]["fallbacks"] = {
            k: v for k, v in m.items()
            if k.startswith("dgraph_mesh_fallbacks_total")}
    run.require(ev["residency_upload_failures"] == 0,
                "dgraph_residency_upload_failures_total != 0")
    if cfg.rehearsal:
        return ev
    run.require(rt["platform"] == "tpu", f"platform is {rt['platform']!r}")
    run.require(not rt["pallas_interpret"],
                "Pallas kernels are in interpret mode")
    for f in want_fams:
        run.require((fams.get(f, {}).get("compile_ms") or 0) > 0,
                    f"/debug/compiles: family {f} has no compile_ms")
    hbm = prof.get("hbm") or {}
    run.require(bool(hbm.get("capable")),
                "devprof: the backend reports no memory_stats()")
    run.require((hbm.get("high_water") or {}).get("device", 0) > 0,
                "devprof: HBM high-water is zero")
    if cfg.mesh:
        run.require(m.get("dgraph_mesh_devices") == rt["device_count"] >= 4,
                    f"dgraph_mesh_devices={m.get('dgraph_mesh_devices')} "
                    f"over {rt['device_count']} devices")
        run.require((m.get("dgraph_mesh_sharded_tablets") or 0) >= 1,
                    "no mesh-sharded tablet")
        # every shard of `follows` is padded to the heaviest shard, so
        # each device holds at least an even share of the int32 edge
        # array; replicated arguments (node-sized) are far below that
        share = 4 * edges // rt["device_count"]
        ev["mesh"]["tablet_share_bytes"] = share
        for d in rt["devices"]:
            run.require(d["peak_bytes_in_use"] >= share,
                        f"device {d['id']} peaked at "
                        f"{d['peak_bytes_in_use']} bytes, below its "
                        f"{share}-byte share of the sharded tablet")
        for r in recs:
            if r["query"].startswith(("hop", "chain", "rec", "sp")):
                run.require(r["mesh_dispatches"] == 1,
                            f"{r['query']}: {r['mesh_dispatches']} mesh "
                            f"dispatches, 1 expected")
                run.require(r["mesh_fallbacks"] == 0,
                            f"{r['query']}: mesh fallback recorded")
    else:
        run.require(rt["devices"][0]["peak_bytes_in_use"] > 0,
                    "memory_stats() peak_bytes_in_use is zero")
    return ev


def dump(name: str, obj) -> None:
    """Debug surfaces kept for the builder (chiprun_out/ comes back)."""
    os.makedirs(DUMP_DIR, exist_ok=True)
    with open(os.path.join(DUMP_DIR, name), "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f, indent=1, default=str)


def dump_server(srv: Server, tag: str) -> None:
    for path, name in (("/debug/compiles", "compiles"),
                       ("/debug/metrics", "metrics"),
                       ("/debug/top?window=86400&n=500", "top"),
                       ("/debug/timeline?view=raw&n=512", "timeline")):
        dump(f"{tag}_{name}.json", srv.call("GET", path))
    dump(f"{tag}_prom.txt", srv.call("GET", "/metrics", raw=True))


# -- the run ------------------------------------------------------------------

def run_smoke(run: Run) -> None:
    cfg = run.cfg
    rng = np.random.default_rng(cfg.seed)
    summary = run.summary

    with Phase(run, "generate") as ph:
        g = Graph(*rmat_csr(cfg.scale, cfg.edge_factor, seed=cfg.seed),
                  seed=cfg.seed)
        rdf = os.path.join(run.workdir, "graph.rdf")
        schema = os.path.join(run.workdir, "schema.txt")
        quads = write_rdf(g, rdf)
        with open(schema, "w") as f:
            f.write(SCHEMA)
        ph["edges"], ph["subjects"] = len(g.indices), len(g.subjects)
        ph["quads"] = quads
        ph["rdf_mb"] = round(os.path.getsize(rdf) / 1e6, 1)

    with Phase(run, "bulk") as ph:
        # host only: the parent decides loaded|built before any child can
        codec = native.status()
        ph["native_codec"] = summary["native_codec"] = codec
        run.require(codec != "unavailable", "native_codec: unavailable")
        postings = os.path.join(run.workdir, "p")
        res = subprocess.run(
            [sys.executable, "-m", "dgraph_tpu", "bulk", "-f", rdf, "-s",
             schema, "-o", postings], cwd=HERE, capture_output=True,
            text=True)
        ph["tail"] = res.stdout.strip().splitlines()[-1:] \
            if res.stdout else []
        if res.returncode != 0:
            raise RuntimeError(f"bulk exited {res.returncode}: "
                               f"{(res.stdout + res.stderr)[-2000:]}")
        os.unlink(rdf)

    with Phase(run, "serve_cold") as ph:
        srv = Server(run, postings, "cold")
        ph["banner"] = srv.banner
        rt = srv.call("GET", "/debug/compiles")["runtime"]
        ph["runtime"] = {k: rt[k] for k in (
            "platform", "device_kind", "device_count", "default_backend",
            "pallas_interpret", "compile_cache_dir", "native_codec")}
        summary.update(
            jax=rt["jax"], jaxlib=rt["jaxlib"], libtpu=rt["libtpu"],
            platform=rt["platform"], device_kind=rt["device_kind"],
            device_count=rt["device_count"],
            default_backend=rt["default_backend"],
            compile_cache_dir=rt["compile_cache_dir"])
        run.require(rt["native_codec"] != "unavailable",
                    "server: native_codec unavailable")
        if not cfg.rehearsal and rt["platform"] != "tpu":
            raise RuntimeError(f"serve came up on {rt['platform']!r}")

    try:
        with Phase(run, "battery") as ph:
            plan = battery(run, srv, g, rng)
            ph["queries"] = plan["records"]
        with Phase(run, "device_evidence") as ph:
            ev = check_device_evidence(run, srv, plan["records"],
                                       len(g.indices))
            ph.fields.update(ev)
            summary["cold_compile_seconds"] = round(
                (ev["compile_ms_total"] or 0) / 1e3, 3)
            # a cache dir placed from outside may arrive warm: the cold
            # run then compiled nothing and a ratio would mean nothing
            cold_was_cold = ev["persistent_cache"]["misses"] > 0
            summary["cold_run_compiled"] = cold_was_cold
            dump_server(srv, "cold")

        # after the read battery: a delta overlay on `follows` would take
        # it off the kernel path until compaction
        with Phase(run, "write_readback") as ph:
            seeds0 = plan["recurse_seeds"][0]
            s0, t0 = pick_write_edge(g, seeds0, rt["device_count"]
                                     if cfg.mesh else 1)
            readback = (f"{{ rb(func: uid({hex(s0)})) {{ follows "
                        f"@filter(uid({hex(t0)})) {{ uid }} }} }}")
            want_rb = [{"follows": [{"uid": hex(t0)}]}]
            before, _ = srv.query(readback)
            run.require(not before.get("rb"),
                        "edge present before the write")
            ack = srv.call("POST", "/mutate?commitNow=true",
                           f"{{ set {{ <{hex(s0)}> <follows> <{hex(t0)}> . "
                           f"}} }}")
            run.require("data" in ack, f"mutation not acknowledged: {ack}")
            after, _ = srv.query(readback)
            run.require(after.get("rb") == want_rb,
                        f"acknowledged write not read back: {after}")
            ph["edge"] = [hex(s0), hex(t0)]
            cold_row = srv.cost_row(q_recurse("rec0", seeds0)) or {}
    finally:
        with Phase(run, "stop"):
            srv.stop()

    with Phase(run, "restart") as ph:
        srv = Server(run, postings, "warm")
        ph["banner"] = srv.banner
    try:
        with Phase(run, "restart_readback") as ph:
            after, _ = srv.query(readback)
            run.require(after.get("rb") == want_rb,
                        f"write lost across the restart: {after}")
        with Phase(run, "restart_battery") as ph:
            # the same text as the cold run's first recurse, over the
            # graph that now holds the acknowledged edge out of a seed
            q = q_recurse("rec0", seeds0)
            check, _ = check_recurse(g.with_edge(s0, t0), "rec0", seeds0)
            rec = run_query(run, srv, "rec0_restart", q,
                            "mesh.recurse" if cfg.mesh
                            else "pb.recurse_fused", check,
                            need_bytes=not cfg.mesh)
            ph["query"] = rec
            cold_ms = float(cold_row.get("compile_ms") or 0.0)
            warm_ms = float(rec.get("compile_ms") or 0.0)
            ph["cold_compile_ms"], ph["warm_compile_ms"] = cold_ms, warm_ms
            summary["warm_restart_compile_seconds"] = round(warm_ms / 1e3, 3)
            summary["cold_same_query_compile_seconds"] = round(
                cold_ms / 1e3, 3)
            pcache = srv.call("GET", "/debug/compiles")["persistent_cache"]
            ph["persistent_cache"] = pcache
            if not cfg.rehearsal:
                run.require(pcache["hits"] >= 1,
                            f"no persistent compile cache hit after the "
                            f"restart: {pcache}")
            if not cfg.rehearsal and cold_was_cold:
                run.require(cold_ms > 0, "cold run booked no compile time")
                run.require(warm_ms <= 0.2 * cold_ms,
                            f"backend compile did not collapse: "
                            f"{warm_ms:.0f} ms after restart vs "
                            f"{cold_ms:.0f} ms cold")
            dump_server(srv, "warm")
    finally:
        with Phase(run, "stop_restart"):
            srv.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=Config.seed,
                    help="seeds the graph, the values and every query")
    ap.add_argument("--mesh", action="store_true",
                    help="start the child as `serve --mesh` (four chips)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dry run at toy scale; can never pass")
    args = ap.parse_args(argv)
    cfg = Config(seed=args.seed, mesh=args.mesh, rehearsal=args.rehearsal)
    if cfg.rehearsal:
        # toy, but past SHARD_MIN_EDGES so `--mesh` still shards a tablet
        cfg.scale, cfg.edge_factor = 13, 16
    workdir = tempfile.mkdtemp(prefix="dgraph-tpu-smoke-")
    run = Run(cfg, workdir)
    emit({"chip_smoke": "start", "scale": cfg.scale,
          "edge_factor": cfg.edge_factor, "seed": cfg.seed,
          "mesh": cfg.mesh, "rehearsal": cfg.rehearsal, "cut": SCALE_CUT,
          "workdir": workdir})
    t0 = time.perf_counter()
    try:
        run_smoke(run)
    except Exception as e:  # noqa: BLE001 — top boundary: report, exit 1
        # whatever raised, inside a phase or between two, the run did not
        # reach its end: that alone is a failure
        run.failures.append(f"aborted: {type(e).__name__}: {e}")
        traceback.print_exc()
    finally:
        for p in run.procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        for name in sorted(os.listdir(workdir)):
            if name.startswith("serve_") and name.endswith(".log"):
                with open(os.path.join(workdir, name),
                          errors="replace") as f:
                    dump(name, f.read()[-20000:])
        shutil.rmtree(workdir, ignore_errors=True)
    skipped = [p for p in PHASES if p not in run.timings]
    if skipped:
        run.failures.append(f"phases not run: {', '.join(skipped)}")
    summary = run.summary
    emit({"summary": "chip_smoke", "rehearsal": cfg.rehearsal,
          "mesh": cfg.mesh, "scale": f"rmat{cfg.scale}x{cfg.edge_factor}",
          "cut": SCALE_CUT, "seed": cfg.seed, **summary,
          "smoke_timings_seconds": run.timings,
          "total_seconds": round(time.perf_counter() - t0, 1),
          "failures": run.failures, "not_run": ["similar_to (vector)"],
          "claim": None})
    if run.failures:
        return 1
    if cfg.rehearsal:
        return 0
    emit({"ok": True, "device": {"platform": summary["platform"],
                                 "kind": summary["device_kind"],
                                 "count": summary["device_count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
