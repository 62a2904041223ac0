"""Hang-safe multi-process launch helper for multihost tests.

tests/test_multihost.py grew four near-identical Popen blocks — spawn N
rank processes, drain their output, time them out together, kill
whatever leaks. The chaos harness (testing/chaos.py) needs the same
shape plus per-rank wall-clock timing (its watchdog assertions compare
rank exit times), so the pattern lives here once.

Guarantees:

- every spawned process is killed before `run_ranks` returns, no
  matter which assertion or exception fires (leaked children are how a
  single red test wedges a whole CI run);
- each rank's stdout+stderr is drained CONCURRENTLY (a rank blocked on
  a full pipe deadlocks against a sequential reader);
- per-rank wall durations are measured from a common start, so "the
  survivor exited within 2x the deadline of the death" is assertable.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

__all__ = ["RankResult", "free_port", "rank_env", "run_ranks",
           "repo_root", "python_argv"]


@dataclass
class RankResult:
    """Outcome of one rank process."""
    rank: int
    returncode: Optional[int]        # None only when timed_out
    output: str                      # merged stdout+stderr
    duration_s: float                # spawn -> exit (or kill)
    timed_out: bool = False

    def tail(self, n: int = 3000) -> str:
        return self.output[-n:]


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def rank_env(rank: int, **extra: str) -> Dict[str, str]:
    """Environment for one CPU-backed rank process: virtual 4-device
    host platform, the rank marker the conftest-free workers read, and
    any TEST_* extras. Pinned to CPU: a child never reaches for a chip
    its parent may hold."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               LIGHTGBM_TPU_MACHINE_RANK=str(rank))
    for key, val in extra.items():
        env[key] = str(val)
    return env


def run_ranks(argvs: Sequence[Sequence[str]], *,
              envs: Sequence[Dict[str, str]],
              cwd: Optional[str] = None,
              timeout: float = 420.0) -> List[RankResult]:
    """Run one process per rank to completion under a SHARED deadline.

    `argvs[i]` is rank i's command line, `envs[i]` its environment
    (build with `rank_env`). On deadline expiry every still-running
    process is killed and its result marked `timed_out`; on any
    exception the finally clause kills the lot — children cannot
    outlive the call."""
    if len(argvs) != len(envs):
        raise ValueError("argvs and envs must pair up rank by rank")
    procs: List[subprocess.Popen] = []
    results: List[Optional[RankResult]] = [None] * len(argvs)
    start = time.monotonic()

    def _drain(i: int, p: subprocess.Popen) -> None:
        out, _ = p.communicate()        # blocks until process exit
        results[i] = RankResult(
            rank=i, returncode=p.returncode,
            output=(out or b"").decode(errors="replace"),
            duration_s=time.monotonic() - start)

    threads: List[threading.Thread] = []
    try:
        for i, argv in enumerate(argvs):
            p = subprocess.Popen(list(argv), env=envs[i], cwd=cwd,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT)
            procs.append(p)
            th = threading.Thread(target=_drain, args=(i, p),
                                  daemon=True)
            th.start()
            threads.append(th)
        deadline = start + timeout
        for th in threads:
            th.join(max(0.0, deadline - time.monotonic()))
        if any(th.is_alive() for th in threads):
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for th in threads:          # communicate() returns post-kill
                th.join(timeout=15.0)
    finally:
        for p in procs:                  # belt and braces: never leak
            if p.poll() is None:
                p.kill()
    out: List[RankResult] = []
    for i in range(len(argvs)):
        r = results[i]
        if r is None:                    # drain never finished: timeout
            r = RankResult(rank=i, returncode=None, output="",
                           duration_s=time.monotonic() - start,
                           timed_out=True)
        out.append(r)
    return out


def repo_root() -> str:
    """Repository root (the directory holding the package), for worker
    scripts that sys.path-insert it."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def python_argv(script_path: str) -> List[str]:
    return [sys.executable, script_path]
