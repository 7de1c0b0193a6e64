"""Launcher-layer tests: opts surface, per-cluster command plans, and an
end-to-end ``--cluster=local`` job doing a real tracker-brokered allreduce.

The reference ships NO tests for its tracker/ layer (SURVEY §4); this suite
is the loopback coverage SURVEY §4 calls out as a gap.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from dmlc_tpu.tracker.opts import get_opts, get_memory_mb, get_cache_file_set
from dmlc_tpu.tracker.launchers import get_launcher
from dmlc_tpu.tracker.launchers import (
    kubernetes as kube_launcher,
    mesos as mesos_launcher,
    mpi as mpi_launcher,
    sge as sge_launcher,
    slurm as slurm_launcher,
    ssh as ssh_launcher,
    tpu as tpu_launcher,
    yarn as yarn_launcher,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    return get_opts(argv)


class TestOpts:
    def test_memory_parse(self):
        assert get_memory_mb("1g") == 1024
        assert get_memory_mb("512m") == 512
        assert get_memory_mb("2048") == 2048
        assert get_memory_mb("1.5g") == 1536

    def test_basic_surface(self):
        args = parse(
            ["--cluster", "local", "-n", "4", "-s", "2",
             "--worker-memory", "2g", "--env", "FOO=bar", "echo", "hi"]
        )
        assert args.cluster == "local"
        assert args.num_workers == 4
        assert args.num_servers == 2
        assert args.worker_memory_mb == 2048
        assert args.env_map == {"FOO": "bar"}
        assert args.command == ["echo", "hi"]

    def test_cluster_from_env(self, monkeypatch):
        monkeypatch.setenv("DMLC_SUBMIT_CLUSTER", "local")
        args = parse(["-n", "1", "true"])
        assert args.cluster == "local"

    def test_no_command_rejected(self):
        with pytest.raises(ValueError):
            parse(["--cluster", "local", "-n", "1"])

    def test_auto_file_cache(self, tmp_path, monkeypatch):
        script = tmp_path / "train.py"
        script.write_text("print('hi')\n")
        args = parse(["--cluster", "local", "-n", "1", str(script), "--lr=1"])
        fset, cmd = get_cache_file_set(args)
        assert str(script) in fset
        assert cmd == ["python train.py", "--lr=1"]

    def test_unknown_cluster(self):
        with pytest.raises(SystemExit):
            parse(["--cluster", "nope", "-n", "1", "true"])

    def test_get_launcher_unknown(self):
        with pytest.raises(ValueError):
            get_launcher("nope")


ENVS = {"DMLC_TRACKER_URI": "10.0.0.1", "DMLC_TRACKER_PORT": 9091,
        "DMLC_NUM_WORKER": 2, "DMLC_NUM_SERVER": 1}


class TestPlans:
    def test_ssh_plan(self, tmp_path):
        hostfile = tmp_path / "hosts"
        hostfile.write_text("10.0.0.2\n10.0.0.3:2222\n# comment\n")
        args = parse(["--cluster", "ssh", "-n", "2", "-s", "1",
                      "-H", str(hostfile), "./train"])
        tasks = ssh_launcher.plan(args, 2, 1, ENVS)
        assert len(tasks) == 3
        roles = [t[0] for t in tasks]
        assert roles == ["worker", "worker", "server"]
        argv = tasks[1][2]
        assert argv[0] == "ssh" and "-p" in argv
        assert argv[argv.index("-p") + 1] == "2222"
        remote = argv[-1]
        assert "export DMLC_ROLE=worker;" in remote
        assert "export DMLC_TASK_ID=1;" in remote
        assert "export DMLC_TRACKER_URI=10.0.0.1;" in remote
        assert remote.endswith("./train")
        # server task round-robins back to first host
        assert tasks[2][2][argv.index("-p") + 1] == "22"
        assert "export DMLC_ROLE=server;" in tasks[2][2][-1]

    def test_mpi_plan_openmpi_and_mpich(self):
        args = parse(["--cluster", "mpi", "-n", "3", "./train"])
        (argv,) = mpi_launcher.plan(args, 3, 0, ENVS, flavor="openmpi")
        assert argv[:3] == ["mpirun", "-n", "3"]
        assert "-x" in argv and "DMLC_ROLE=worker" in argv
        assert argv[-1] == "./train"
        (argv2,) = mpi_launcher.plan(args, 3, 0, ENVS, flavor="mpich")
        assert "-env" in argv2
        i = argv2.index("DMLC_ROLE")
        assert argv2[i + 1] == "worker"

    def test_slurm_plan(self):
        args = parse(["--cluster", "slurm", "-n", "4", "-s", "2",
                      "--slurm-worker-nodes", "2", "--worker-cores", "3",
                      "./train"])
        plans = slurm_launcher.plan(args, 4, 2, ENVS)
        assert len(plans) == 2
        w = plans[0]
        assert w[0] == "env" and "--ntasks=4" in w and "--nodes=2" in w
        assert "--cpus-per-task=3" in w
        assert "DMLC_ROLE=worker" in w and w.index("DMLC_ROLE=worker") < w.index("srun")
        s = plans[1]
        assert "--ntasks=2" in s and "DMLC_ROLE=server" in s

    def test_sge_script_and_qsub(self):
        args = parse(["--cluster", "sge", "-n", "2", "-s", "1",
                      "--queue", "gpuq", "./train"])
        env = {"DMLC_TRACKER_URI": "10.0.0.1"}
        text = sge_launcher.plan_run_script(env, "./train", 2, 1)
        assert "SGE_TASK_ID" in text
        assert "export DMLC_ROLE=worker" in text
        assert "export DMLC_ROLE=server" in text
        assert text.strip().endswith("./train")
        argv = sge_launcher.plan_qsub("rundmlc.sh", 3, "gpuq", 1, None, "j")
        assert "-t" in argv and argv[argv.index("-t") + 1] == "1-3"
        assert "gpuq" in argv

    def test_kubernetes_manifests(self):
        args = parse(["--cluster", "kubernetes", "-n", "2", "-s", "1",
                      "--jobname", "myjob", "--kube-namespace", "ns1",
                      "./train"])
        manifests = kube_launcher.plan(args, 2, 1, ENVS)
        kinds = [m["kind"] for m in manifests]
        assert kinds == ["Service", "Job", "Job"]
        svc, server_job, worker_job = manifests
        assert svc["spec"]["ports"][0]["port"] == 9091
        assert worker_job["spec"]["completions"] == 2
        assert worker_job["spec"]["completionMode"] == "Indexed"
        assert worker_job["metadata"]["namespace"] == "ns1"
        env_names = [e["name"] for e in
                     worker_job["spec"]["template"]["spec"]["containers"][0]["env"]]
        assert "DMLC_TRACKER_URI" in env_names
        assert "DMLC_TASK_ID" in env_names
        json.dumps(manifests)  # must be serializable for kubectl apply

    def test_mesos_plan(self):
        args = parse(["--cluster", "mesos", "-n", "2",
                      "--mesos-master", "zk://m:5050", "--worker-memory",
                      "2g", "./train"])
        tasks = mesos_launcher.plan(args, 2, 0, ENVS)
        assert len(tasks) == 2
        assert tasks[0]["mem_mb"] == 2048
        assert tasks[1]["env"]["DMLC_TASK_ID"] == "1"

    def test_yarn_plan(self):
        args = parse(["--cluster", "yarn", "-n", "2", "-s", "1",
                      "--queue", "q", "./train"])
        argv = yarn_launcher.plan_hadoop_jar(args, 2, 1, ENVS, "/tmp/am.jar")
        assert argv[:2] == ["hadoop", "jar"]
        assert "/tmp/am.jar" in argv
        joined = " ".join(argv)
        assert "DMLC_NUM_WORKER=2" in joined
        assert "DMLC_MAX_ATTEMPT=3" in joined


class TestTpuLauncher:
    def test_discover_hosts_precedence(self, tmp_path, monkeypatch):
        args = parse(["--cluster", "tpu", "-n", "2",
                      "--tpu-hosts", "tpu-a,tpu-b", "./train"])
        assert tpu_launcher.discover_hosts(args) == [("tpu-a", 22), ("tpu-b", 22)]
        hostfile = tmp_path / "hosts"
        hostfile.write_text("tpu-c:2222\n")
        args2 = parse(["--cluster", "tpu", "-n", "1", "-H", str(hostfile),
                       "./train"])
        assert tpu_launcher.discover_hosts(args2) == [("tpu-c", 2222)]
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "tpu-d,tpu-e")
        args3 = parse(["--cluster", "tpu", "-n", "2", "./train"])
        assert tpu_launcher.discover_hosts(args3) == [("tpu-d", 22), ("tpu-e", 22)]
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES")
        args4 = parse(["--cluster", "tpu", "-n", "1", "./train"])
        assert tpu_launcher.discover_hosts(args4) == [("localhost", 22)]

    def test_plan_exports_jax_contract(self):
        args = parse(["--cluster", "tpu", "-n", "2",
                      "--tpu-hosts", "tpu-a,tpu-b",
                      "--tpu-coordinator-port", "9999", "./train"])
        tasks = tpu_launcher.plan(args, 2, 0, ENVS)
        assert len(tasks) == 2
        _, _, tid0, env0, argv0 = tasks[0]
        _, _, tid1, env1, argv1 = tasks[1]
        assert env0["DMLC_TPU_COORDINATOR"] == "tpu-a:9999"
        assert env0["DMLC_TPU_NUM_PROC"] == "2"
        assert env0["DMLC_TPU_PROC_ID"] == "0"
        assert env1["DMLC_TPU_PROC_ID"] == "1"
        assert env1["DMLC_JOB_CLUSTER"] == "tpu"
        # remote hosts run over ssh with the env exported in the remote cmd
        assert argv0[0] == "ssh"
        assert "export DMLC_TPU_COORDINATOR=tpu-a:9999;" in argv0[-1]

    def test_plan_localhost_is_local_exec(self):
        args = parse(["--cluster", "tpu", "-n", "1", "./train"])
        ((host, port, tid, env, argv),) = tpu_launcher.plan(args, 1, 0, ENVS)
        assert host == "localhost" and argv is None
        assert env["DMLC_TPU_COORDINATOR"] == "127.0.0.1:8476"

    def test_worker_host_mismatch_rejected(self):
        args = parse(["--cluster", "tpu", "-n", "3",
                      "--tpu-hosts", "a,b", "./train"])
        with pytest.raises(ValueError, match="one worker per TPU host"):
            tpu_launcher.plan(args, 3, 0, ENVS)

    def test_initialize_from_env_noop_single_proc(self, monkeypatch):
        from dmlc_tpu.parallel import distributed

        monkeypatch.delenv("DMLC_TPU_COORDINATOR", raising=False)
        assert distributed.initialize_from_env() is False
        monkeypatch.setenv("DMLC_TPU_COORDINATOR", "127.0.0.1:1")
        monkeypatch.setenv("DMLC_TPU_NUM_PROC", "1")
        assert distributed.initialize_from_env() is False
        assert distributed.env_process_info()["coordinator"] == "127.0.0.1:1"


WORKER_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from dmlc_tpu.collective.socket_engine import SocketEngine
    eng = SocketEngine()
    out = eng.allreduce(np.full(8, eng.rank + 1, dtype=np.float32))
    world = eng.world_size
    ok = np.allclose(out, world * (world + 1) / 2)
    eng.tracker_print(f"rank {{eng.rank}} ok={{ok}}")
    eng.shutdown()
    sys.exit(0 if ok else 1)
""")


class TestLocalEndToEnd:
    def test_dmlc_submit_local_allreduce(self, tmp_path):
        """Full CLI path: dmlc-submit --cluster=local -n 3 <worker>, workers
        rendezvous via the tracker and allreduce through the socket engine
        (the BASELINE 'dmlc-submit local multi-process + Allreduce' smoke)."""
        script = tmp_path / "worker.py"
        script.write_text(WORKER_SCRIPT.format(repo=REPO))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "dmlc-submit"),
             "--cluster", "local", "-n", "3", "--host-ip", "127.0.0.1",
             sys.executable, str(script)],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr
        assert "all 3 workers started" in proc.stderr + proc.stdout

    def test_workers_crash_before_rendezvous_fails_fast(self, tmp_path):
        """All workers dying pre-rendezvous must ABORT the job, not hang.

        The reference tracker joins unconditionally (tracker.py:329-331) and
        hangs forever in this scenario; our local launcher reports task
        liveness to RabitTracker.join, which raises once every worker
        process has exited while the accept loop is still waiting.
        """
        script = tmp_path / "crash.py"
        script.write_text("import sys; sys.exit(3)\n")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "dmlc-submit"),
             "--cluster", "local", "-n", "2", "--host-ip", "127.0.0.1",
             sys.executable, str(script)],
            capture_output=True, text=True, timeout=60,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode != 0
        assert "tracker is still waiting" in proc.stderr

    def test_chip_contention_is_refused_with_the_cause(self, monkeypatch):
        """N local tasks sharing one environment on a TPU host would all
        open the same chips (a chip belongs to one process; on the v5e
        every worker after the first dies on a libtpu lockfile error that
        names no cause) — the launcher refuses up front and says why."""
        from dmlc_tpu.tracker.launchers import local
        from dmlc_tpu.utils.logging import DMLCError

        monkeypatch.setattr(local, "_tpu_chip_nodes",
                            lambda: ["/dev/vfio/0", "/dev/vfio/1"])
        why = local.chip_contention(2, {"JAX_PLATFORMS": "tpu,cpu"})
        assert "a chip belongs to one process" in why
        assert "--cluster=tpu" in why and "JAX_PLATFORMS=cpu" in why
        assert local.chip_contention(2, {}) is not None  # jax default
        # one process, a CPU-pinned world, or no chips: nothing to refuse
        assert local.chip_contention(1, {}) is None
        assert local.chip_contention(4, {"JAX_PLATFORMS": "cpu"}) is None
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        args = parse(["--cluster", "local", "-n", "2", "true"])
        with pytest.raises(DMLCError, match="a chip belongs to one process"):
            local.submit(args)
        monkeypatch.setattr(local, "_tpu_chip_nodes", lambda: [])
        assert local.chip_contention(4, {}) is None

    def test_local_launcher_retry(self, tmp_path):
        """A task failing on attempt 0 succeeds on retry (local.py:25-44).

        Task 0 dies BEFORE rendezvous on its first attempt; the tracker holds
        the job open until the retried task 0 joins task 1 and both finish.
        """
        script = tmp_path / "flaky.py"
        script.write_text(textwrap.dedent(f"""
            import os, sys
            sys.path.insert(0, {REPO!r})
            if (os.environ.get("DMLC_TASK_ID") == "0"
                    and os.environ.get("DMLC_NUM_ATTEMPT") == "0"):
                sys.exit(7)  # fail fast, before touching the tracker
            from dmlc_tpu.collective.socket_engine import SocketEngine
            import numpy as np
            eng = SocketEngine()
            out = eng.allreduce(np.ones(1, dtype=np.float32))
            eng.shutdown()
            sys.exit(0 if float(out[0]) == 2.0 else 1)
        """))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "dmlc-submit"),
             "--cluster", "local", "-n", "2", "--max-attempts", "2",
             "--host-ip", "127.0.0.1", sys.executable, str(script)],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr

    def test_shim_derives_sge_role(self):
        out = subprocess.run(
            [sys.executable, "-m", "dmlc_tpu.tracker.shim",
             "python -c \"import os; print(os.environ['DMLC_ROLE'],"
             " os.environ['DMLC_TASK_ID'])\""],
            capture_output=True, text=True, timeout=60, cwd=REPO,
            env={**os.environ, "SGE_TASK_ID": "3", "DMLC_NUM_WORKER": "2"},
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "server 0"
