"""The pristine store of a (config, seed): generated and bulk-loaded once
into `benchmarks/.cache/store/<config>-<seed>/`, then copied for each run
(the server writes into its directory). A later run with the same seed skips generate + bulk."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time

from harness.graph import Graph, write_rdf

CACHE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache", "store")


def cached_store(root: str, cfg: dict, seed: int, g: Graph,
                 timings: dict, name: str | None = None) -> str:
    """Path of the pristine store, built if absent. Built under a scratch
    name and renamed, so a killed run leaves no half store behind."""
    final = os.path.join(CACHE, f"{name or cfg['name']}-{seed}")
    if os.path.isdir(final):
        timings["store"] = "cached"
        return final
    os.makedirs(CACHE, exist_ok=True)
    tmp = f"{final}.building.{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    try:
        t0 = time.monotonic()
        rdf = os.path.join(tmp, "graph.rdf")
        schema = os.path.join(tmp, "schema.txt")
        quads = write_rdf(g, rdf)
        with open(schema, "w") as f:
            f.write(cfg["data"]["schema"])
        timings["write_rdf_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "-m", "dgraph_tpu", "bulk", "-f", rdf, "-s",
             schema, "-o", os.path.join(tmp, "p")], cwd=root,
            capture_output=True, text=True)
        if res.returncode != 0:
            raise RuntimeError(f"bulk exited {res.returncode}: "
                               f"{(res.stdout + res.stderr)[-2000:]}")
        timings["bulk_s"] = time.monotonic() - t0
        timings["quads"] = quads
        try:
            os.rename(os.path.join(tmp, "p"), final)
        except OSError:
            if not os.path.isdir(final):    # else: a concurrent run built it
                raise
        timings["store"] = "built"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return final


def private_copy(store: str, workdir: str) -> str:
    dst = os.path.join(workdir, "p")
    shutil.copytree(store, dst)
    return dst
