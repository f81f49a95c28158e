"""The one `serve` child — the only process of a run that may touch JAX —
and the JAX-free HTTP client the parent speaks to it with. After
chip_smoke.py's Server (PR 21)."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
import urllib.error
import urllib.request

CLIENT_TIMEOUT_S = 120.0


class HttpError(RuntimeError):
    def __init__(self, method: str, path: str, code: int, body: bytes):
        super().__init__(f"{method} {path.split('?')[0]} -> HTTP {code}: "
                         f"{body[:400]!r}")
        self.code = code


class Server:
    def __init__(self, root: str, postings: str, log_path: str,
                 serve_args: list[str], platform: str, devices: int = 1,
                 wrapper: str | None = None,
                 wrapper_env: dict | None = None) -> None:
        """`wrapper` is a script that runs dgraph_tpu's own entry
        in-process (serve_traced.py for a traced run); without it the
        child is plain `python -m dgraph_tpu serve`."""
        env = dict(os.environ)
        env["PYTHONUNBUFFERED"] = "1"
        env["JAX_PLATFORMS"] = platform
        if platform == "cpu" and devices > 1:       # rehearsal of a mesh
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                f"host_platform_device_count={devices}"
                                ).strip()
        env.update(wrapper_env or {})
        head = [sys.executable, wrapper] if wrapper else \
            [sys.executable, "-m", "dgraph_tpu"]
        args = head + ["serve", "-p", postings, "--port", "0",
                       "--grpc_port", "0"] + list(serve_args)
        self.log_path = log_path
        self.log = open(log_path, "wb")
        self.proc = subprocess.Popen(args, cwd=root, env=env,
                                     stdout=self.log,
                                     stderr=subprocess.STDOUT)
        try:
            self.banner, self.port = self._wait_banner()
        except BaseException:
            self.kill()
            raise

    def _wait_banner(self, timeout: float = 600.0) -> tuple[str, int]:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            text = self.log_tail(1 << 20)
            m = re.search(r"^.*serving HTTP on [\w.]+:(\d+).*$", text, re.M)
            if m:
                return m.group(0), int(m.group(1))
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"serve exited {self.proc.returncode} before its "
                    f"banner:\n{text[-2000:]}")
            time.sleep(0.1)
        raise RuntimeError(f"serve printed no banner in {timeout:.0f}s")

    def log_tail(self, n: int = 4000) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")[-n:]

    def raw(self, method: str, path: str, body: str | None = None,
            timeout: float = CLIENT_TIMEOUT_S) -> bytes:
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}",
            data=body.encode() if body is not None else None, method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            raise HttpError(method, path, e.code, e.read()) from e

    def call(self, method: str, path: str, body: str | None = None,
             timeout: float = CLIENT_TIMEOUT_S):
        return json.loads(self.raw(method, path, body, timeout))

    def prom(self) -> dict[str, float]:
        """/metrics as {series: value}; labelled series keep their braces."""
        out = {}
        for line in self.raw("GET", "/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    def compiles(self) -> dict:
        """/debug/compiles, with `programs_loaded` = backend compiles +
        persistent-cache hits so far: what warm-up waits to go quiet."""
        comp = self.call("GET", "/debug/compiles")
        pc = comp.get("persistent_cache") or {}
        comp["programs_loaded"] = int(comp.get("compiles") or 0) \
            + int(pc.get("hits") or 0)
        return comp

    def counters(self) -> dict:
        """One reading of the surfaces the per-layer readers read."""
        comp = self.compiles()
        return {"prom": self.prom(), "compiles": comp,
                "programs_loaded": comp["programs_loaded"]}

    def trace_kernels(self, trace_id: str) -> list[str] | None:
        """kernel= attrs of every device_kernel span of one trace; None
        when the server's trace ring no longer holds it."""
        try:
            tree = self.call("GET", f"/debug/traces/{trace_id}?view=tree")
        except HttpError:
            return None
        found = []

        def walk(n):
            if n.get("name") == "device_kernel":
                found.append(str(n.get("attrs", {}).get("kernel", "")))
            for c in n.get("children", ()):
                walk(c)

        for n in tree.get("tree", ()):
            walk(n)
        return found

    def stop(self) -> str | None:
        """Clean shutdown over /admin/shutdown; returns what went wrong,
        if anything: a server that has to be signalled is a fault."""
        problem = None
        if self.proc.poll() is None:
            try:
                self.raw("POST", "/admin/shutdown", "", timeout=30)
            except (OSError, RuntimeError):   # listener may close mid-reply
                pass
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                problem = "serve did not exit within 120s of /admin/shutdown"
                self.kill()
        if problem is None and self.proc.returncode != 0:
            problem = f"serve exited {self.proc.returncode}"
        self.log.close()
        return problem

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)
        if not self.log.closed:
            self.log.close()
