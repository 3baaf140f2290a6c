"""Shared subprocess test plumbing (ISSUE 12 satellite).

Every multi-process test (jax.distributed workers, fleet serving
workers) needs the same three things, previously duplicated across
``test_distributed_multiprocess.py`` / ``distributed_worker.py``:

* an ephemeral **free port** for coordinators (fleet workers bind
  ``port=0`` and report back instead — prefer that where possible);
* the **env scrub**: drop ``XLA_FLAGS`` (conftest's 8-virtual-device
  flag would leak into workers that must own exactly one device) and pin
  ``JAX_PLATFORMS=cpu``;
* **communicate-with-timeout** over a set of workers where one hung
  process must kill the whole set, not wedge the suite.

Worker SCRIPTS (run as subprocesses, no conftest) call
:func:`pin_single_cpu_device` before importing jax to apply the same
scrub in-process.
"""

import json
import os
import socket
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

#: distinct exit code a worker uses when joining jax.distributed failed —
#: the spawner asserts the rc + one JSON error line instead of diagnosing
#: a 300 s communicate_all timeout (ISSUE 15 satellite)
INIT_FAILED_RC = 13


def free_port():
    """An ephemeral localhost port (for coordinators that cannot bind
    port 0 themselves, e.g. jax.distributed's coordinator address)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def scrubbed_env(**overrides):
    """Subprocess env with the CPU-pin/device-count scrub applied — ONE
    definition shared with the product's fleet supervisor (its workers
    need the identical scrub), plus the repo root on PYTHONPATH so
    spawned scripts import the package from any cwd."""
    from deeplearning4j_tpu.fleet.supervisor import default_worker_env
    env = default_worker_env()
    env.update(overrides)
    return env


def pin_single_cpu_device():
    """In-process scrub for worker SCRIPTS, called BEFORE importing jax:
    exactly one local CPU device."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)


def spawn(argv, env=None, **popen_kw):
    """Popen a worker with the scrubbed env and piped text stdio."""
    return subprocess.Popen(
        argv, env=env if env is not None else scrubbed_env(),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        **popen_kw)


def communicate_all(procs, timeout=300, fail=None):
    """``communicate()`` every proc under one timeout; a hung worker
    kills the whole set. Returns [(stdout, stderr)] in order; calls
    ``fail(msg)`` (e.g. pytest.fail) or raises on timeout/nonzero rc."""
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            msg = "subprocess worker timed out"
            if fail is not None:
                fail(msg)
            raise RuntimeError(msg)
        if p.returncode != 0:
            msg = f"worker failed rc={p.returncode}:\n{err[-3000:]}"
            if fail is not None:
                fail(msg)
            raise RuntimeError(msg)
        outs.append((out, err))
    return outs


def last_json_line(text):
    """The last JSON object printed on a worker's stdout (workers print
    ONE machine-readable result/ready line last)."""
    return json.loads(text.strip().splitlines()[-1])


def ready_clock(doc):
    """The ``{mono, unix}`` clock pair a worker stamps on its ready line
    (the cluster-timeline alignment seed). Returns None for ready lines
    that predate the clock pair — old lines still parse."""
    clk = (doc or {}).get("clock")
    if isinstance(clk, dict) and clk.get("unix") is not None:
        return clk
    return None
