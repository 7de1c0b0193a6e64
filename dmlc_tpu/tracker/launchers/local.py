"""Local launcher: N processes on this host (tracker/dmlc_tracker/local.py).

Spawns num_workers + num_servers subprocesses, each with the DMLC_* env
contract (DMLC_TASK_ID, DMLC_ROLE, DMLC_JOB_CLUSTER=local — local.py:12-23)
and a per-task retry loop honoring ``--max-attempts`` / ``DMLC_NUM_ATTEMPT``
(local.py:25-44).

This launcher is for CPU worlds on one host (the socket engine, tests,
data-service fleets). Every task gets the SAME environment, so on a host
with TPU chips N jax workers would all try to open the same chips, and a
chip belongs to one process: measured on the v5e host, every worker after
the first dies at backend init with "Unable to initialize backend 'tpu':
ABORTED: Internal error when accessing libtpu multi-process lockfile",
which names no cause. ``submit`` refuses that launch up front instead.
Chip training is one process per host over a ``Mesh`` — ``--cluster=tpu``.
"""

from __future__ import annotations

import glob
import importlib.util
import os
import subprocess
import threading
from typing import Dict, List, Optional

from dmlc_tpu.resilience.preempt import EXIT_PREEMPTED
from dmlc_tpu.tracker.launchers.common import task_env
from dmlc_tpu.tracker.rendezvous import submit_with_tracker
from dmlc_tpu.utils.logging import DMLCError

#: relaunch-after-preemption ceiling: exit-75 restarts do not consume
#: --max-attempts (a preempted task did nothing wrong), but an unbounded
#: loop would hide a task that exits 75 pathologically
MAX_PREEMPT_RELAUNCHES = 32


def _tpu_chip_nodes() -> List[str]:
    """Device nodes of this host's TPU chips (``/dev/accel*`` on older
    generations, numbered ``/dev/vfio`` groups on v5e), if libtpu is
    installed to drive them. The tracker parent imports no jax, so it
    looks at the host, not at ``jax.devices()``."""
    if importlib.util.find_spec("libtpu") is None:
        return []
    return sorted(glob.glob("/dev/accel*")
                  + glob.glob("/dev/vfio/[0-9]*"))


def chip_contention(nproc: int, env: Dict[str, str]) -> Optional[str]:
    """Why ``nproc`` tasks launched with ``env`` cannot all start on this
    host, or None. They can when there is one of them, when the host has
    no TPU, or when ``JAX_PLATFORMS`` keeps them off it."""
    if nproc <= 1:
        return None
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return None
    nodes = _tpu_chip_nodes()
    if not nodes:
        return None
    return (
        "--cluster=local would start %d processes with one environment on "
        "a host with TPU chips (%s); a chip belongs to one process, so "
        "every jax worker after the first fails at backend init (libtpu "
        "lockfile). For chip training use --cluster=tpu (one worker per "
        "host, all chips through one Mesh); for a CPU socket-engine world "
        "set JAX_PLATFORMS=cpu (--env JAX_PLATFORMS=cpu)"
        % (nproc, ", ".join(nodes))
    )


def submit(args) -> None:
    nrepeat = args.max_attempts or int(os.environ.get("DMLC_NUM_ATTEMPT", 1))
    cmd = " ".join(args.command)
    why = chip_contention(
        args.num_workers + (getattr(args, "spares", 0) or 0),
        {**os.environ, **args.env_map})
    if why:
        raise DMLCError(why)
    threads: List[threading.Thread] = []

    def run_task(task_id: int, role: str, envs: Dict[str, object],
                 spare: bool = False) -> None:
        extra = dict(args.env_map)
        if spare:
            # DMLC_TPU_SPARE makes collective.init() park on the tracker's
            # join handshake instead of rendezvousing immediately
            extra["DMLC_TPU_SPARE"] = "1"
        env = task_env(envs, task_id, role, "local", extra=extra)
        attempts = max(1, nrepeat)
        preempt_relaunches = 0
        while attempts > 0:
            full = os.environ.copy()
            full.update(env)
            full["DMLC_NUM_ATTEMPT"] = str(max(1, nrepeat) - attempts)
            code = subprocess.Popen(cmd, env=full, shell=True).wait()
            if code == 0:
                return
            if (code == EXIT_PREEMPTED
                    and preempt_relaunches < MAX_PREEMPT_RELAUNCHES):
                # the preemption handler committed a job snapshot and
                # exited with the relaunch code: restart WITHOUT burning
                # a retry attempt — the relaunched task resumes from the
                # committed manifest (docs/robustness.md)
                preempt_relaunches += 1
                print(f"{role} {task_id} preempted (exit {code}); "
                      f"relaunching to resume from its job snapshot "
                      f"(relaunch {preempt_relaunches})")
                continue
            flight_dir = full.get("DMLC_TPU_FLIGHTREC")
            if flight_dir:
                print(f"{role} {task_id} exited {code}; flight-recorder "
                      f"dump (if any): "
                      f"{flight_dir}/flightrec-rank{task_id}.json")
            attempts -= 1
            if attempts > 0:
                print(f"{role} {task_id} exited {code}; retrying "
                      f"({attempts} attempts left)")

    def fun_submit(nworker: int, nserver: int, envs: Dict[str, object]) -> None:
        for i in range(nworker + nserver):
            role = "worker" if i < nworker else "server"
            tid = i if i < nworker else i - nworker
            t = threading.Thread(
                target=run_task, args=(tid, role, envs), daemon=True
            )
            t.start()
            threads.append(t)
        # warm spares: worker-role tasks beyond the base world, with task
        # ids (= rabit jobids) that can never collide with real workers
        for j in range(max(0, getattr(args, "spares", 0) or 0)):
            t = threading.Thread(
                target=run_task, args=(nworker + j, "worker", envs),
                kwargs={"spare": True}, daemon=True,
            )
            t.start()
            threads.append(t)

    submit_with_tracker(
        args.num_workers,
        args.num_servers,
        fun_submit,
        host_ip=args.host_ip or "auto",
        # threads own the worker processes: once they are all done while the
        # tracker still waits, the job can never finish — fail fast.
        tasks_alive=lambda: any(t.is_alive() for t in threads),
    )
    for t in threads:
        t.join()
