"""chip_smoke.py's CPU rehearsal, the compile-cache rule, and the
one-process-per-chip rule for host-only subcommands.

The rehearsal drives every phase of the smoke (generate, bulk, serve, the
HTTP battery against the numpy reference, write + read-back, restart +
read-back + re-run) at toy scale on XLA:CPU. It proves the script's logic,
never the chip: it must refuse to print the pass line, and a wrong answer
or a failed phase must exit non-zero.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from dgraph_tpu.models.rmat import rmat_csr, rmat_edges
from dgraph_tpu.utils import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = ["generate", "bulk", "serve_cold", "battery", "device_evidence",
          "write_readback", "stop", "restart", "restart_readback",
          "restart_battery", "stop_restart"]


def _run(code_or_args, timeout=600):
    cmd = [sys.executable] + code_or_args
    res = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                         timeout=timeout)
    lines = [json.loads(ln) for ln in res.stdout.splitlines()
             if ln.startswith("{")]
    return res, lines


def _patched(patch: str) -> list[str]:
    return ["-c", "import sys; import chip_smoke as cs\n" + patch
            + "\nsys.exit(cs.main(['--rehearsal']))"]


def test_rehearsal_runs_every_phase_and_never_passes():
    res, lines = _run(["chip_smoke.py", "--rehearsal"])
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    phases = [ln["phase"] for ln in lines if "phase" in ln]
    assert phases == PHASES
    assert not any("failed" in ln for ln in lines if "phase" in ln)
    battery = next(ln for ln in lines if ln.get("phase") == "battery")
    names = [q["query"] for q in battery["queries"]]
    assert names[:6] == ["hop0", "hop1", "chain0", "chain1", "rec0", "rec1"]
    assert sum(n.startswith("sp") for n in names) == 10
    assert names[-2:] == ["gb0", "gb1"]
    assert all(q["correct"] for q in battery["queries"])
    restart = next(ln for ln in lines if ln.get("phase") == "restart_battery")
    assert restart["query"]["correct"]
    # the last line is the summary, not the pass line — and no line is
    summary = lines[-1]
    assert summary["summary"] == "chip_smoke" and summary["rehearsal"] is True
    assert summary["failures"] == [] and summary["platform"] == "cpu"
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert not any(ln.get("ok") for ln in lines)
    assert '"ok"' not in res.stdout


def test_wrong_answer_exits_nonzero():
    res, lines = _run(_patched(
        "orig = cs.ref_groupby\n"
        "cs.ref_groupby = lambda g: {k: (c + 1, a) "
        "for k, (c, a) in orig(g).items()}"))
    assert res.returncode != 0
    failed = [ln["check_failed"] for ln in lines if "check_failed" in ln]
    assert any(f.startswith("gb0: answer != numpy reference")
               for f in failed), failed
    assert lines[-1]["failures"] and not any(ln.get("ok") for ln in lines)


def test_phase_exception_exits_nonzero():
    res, lines = _run(_patched(
        "def boom(*a, **k): raise OSError('injected')\n"
        "cs.write_rdf = boom"))
    assert res.returncode != 0
    gen = next(ln for ln in lines if ln.get("phase") == "generate")
    assert gen["failed"].startswith("OSError")
    # the run stops at the failed phase: nothing later ran, nothing passed
    assert [ln["phase"] for ln in lines if "phase" in ln] == ["generate"]
    assert lines[-1]["failures"][:2] == ["phase generate: OSError",
                                         "aborted: OSError: injected"]
    assert not any(ln.get("ok") for ln in lines)


def test_exception_in_edge_selection_exits_nonzero():
    """pick_write_edge raises for graphs it cannot place an edge in (any
    --seed reaches it). The run must not end as a pass with the write,
    restart and compile-cache phases silently skipped."""
    res, lines = _run(_patched(
        "def boom(*a, **k): raise RuntimeError('injected')\n"
        "cs.pick_write_edge = boom"))
    assert res.returncode != 0
    phases = [ln["phase"] for ln in lines if "phase" in ln]
    assert phases[-2:] == ["write_readback", "stop"]
    failures = lines[-1]["failures"]
    assert "phase write_readback: RuntimeError" in failures
    assert "aborted: RuntimeError: injected" in failures
    assert any(f.startswith("phases not run: restart,") for f in failures)
    assert not any(ln.get("ok") for ln in lines)


def test_exception_between_phases_exits_nonzero():
    """Nothing run_smoke raises may end as exit 0 — also code that sits
    outside every Phase (here: the Phase constructor itself, after the
    battery)."""
    res, lines = _run(_patched(
        "orig = cs.Phase.__init__\n"
        "def init(self, run, name):\n"
        "    if name == 'write_readback': raise RuntimeError('between')\n"
        "    orig(self, run, name)\n"
        "cs.Phase.__init__ = init"))
    assert res.returncode != 0
    failures = lines[-1]["failures"]
    assert failures[0] == "aborted: RuntimeError: between"
    assert not any("failed" in ln for ln in lines if "phase" in ln)
    assert not any(ln.get("ok") for ln in lines)


def test_server_that_must_be_signalled_is_a_failure(monkeypatch):
    """/admin/shutdown that does not end the child is recorded, not
    papered over by terminate()."""
    import subprocess

    import chip_smoke as cs

    run = cs.Run(cs.Config(rehearsal=True), workdir="")
    srv = object.__new__(cs.Server)
    srv.run, srv.tag = run, "cold"
    srv.log = open(os.devnull, "wb")
    srv.proc = subprocess.Popen([sys.executable, "-c",
                                 "import time; time.sleep(600)"])
    monkeypatch.setattr(srv, "call", lambda *a, **k: {})
    real_wait, waits = srv.proc.wait, []

    def wait(timeout=None):
        waits.append(timeout)
        return real_wait(timeout=0.2 if len(waits) == 1 else timeout)

    monkeypatch.setattr(srv.proc, "wait", wait)
    srv.stop()
    assert srv.proc.poll() is not None
    assert any("did not exit within 120s" in f for f in run.failures)


# -- the smoke's graph ---------------------------------------------------------

def test_rmat_dedup_equals_row_unique():
    """rmat_edges dedups on a packed int64 key; the result is the row-wise
    unique it replaced — same edges, same (src, dst) order — so the graph
    every record names did not move."""
    raw = rmat_edges(12, 8, seed=7, dedup=False)
    want = np.unique(raw, axis=0)
    got = rmat_edges(12, 8, seed=7)
    assert len(want) < len(raw)             # there were duplicates to drop
    assert got.dtype == want.dtype and np.array_equal(got, want)
    subjects, indptr, indices = rmat_csr(12, 8, seed=7)
    assert np.array_equal(np.repeat(subjects, np.diff(indptr)),
                          want[:, 0] + 1)
    assert np.array_equal(indices, want[:, 1] + 1)


# -- compile cache: placed from outside, or one normalised default ------------

def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_env_set_means_code_sets_none(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    calls = _record_updates(monkeypatch)
    runtime.configure_compile_cache()
    assert "jax_compilation_cache_dir" not in [n for n, _ in calls]


def test_cache_dir_default_is_normalised_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    recorded = _record_updates(monkeypatch)
    runtime.configure_compile_cache()
    calls = dict(recorded)
    path = calls["jax_compilation_cache_dir"]
    assert path == os.path.join(REPO, ".jax_cache")
    assert os.path.isabs(path) and os.path.normpath(path) == path
    assert ".." not in path.split(os.sep)
    # the thresholds conftest used to set travel with the function
    assert calls["jax_persistent_cache_min_entry_size_bytes"] == -1
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


# -- one process per chip: host-only subcommands stay off JAX -----------------

def test_bulk_leaves_jax_unimported_and_zero_takes_no_backend(tmp_path):
    """bulk never imports jax. zero does import it — its gRPC options and
    worker client live in parallel/remote.py beside the worker service —
    but, placement controller running or not, never initialises a
    backend: the chip stays free for its workers."""
    rdf = tmp_path / "g.rdf"
    rdf.write_text('<0x1> <follows> <0x2> .\n<0x1> <name> "a" .\n')
    res, _ = _run(["-c", (
        "import sys, threading, time\n"
        "from dgraph_tpu.__main__ import main\n"
        "from dgraph_tpu.utils import runtime\n"
        f"assert main(['bulk', '-f', {str(rdf)!r}, '-o', "
        f"{str(tmp_path / 'p')!r}, '-j', '1']) == 0\n"
        "assert 'jax' not in sys.modules, 'bulk imported jax'\n"
        "def zero(*extra):\n"
        "    t = threading.Thread(target=main, daemon=True,\n"
        "                         args=(['zero', '--port', '0', *extra],))\n"
        "    t.start(); time.sleep(2.0)\n"
        "    assert t.is_alive(), 'zero exited'\n"
        "zero()\n"
        "zero('--rebalance_interval_s', '0.2')\n"
        "assert 'jax' in sys.modules     # else the check below is vacuous\n"
        "assert not runtime.backend_initialized(), 'zero took a backend'\n"
        "runtime.assert_host_only('zero')\n")])
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]


def test_host_only_assertion_trips_on_an_initialised_backend():
    # this test process initialised the CPU backend in conftest
    assert runtime.backend_initialized()
    with pytest.raises(RuntimeError, match="host-only"):
        runtime.assert_host_only("bulk")
