"""Launcher tests (reference analogue: tests/unit/launcher/test_ds_arguments.py)."""

import pytest

from deepspeed_tpu.launcher import fetch_hostfile, parse_inclusion_exclusion
from deepspeed_tpu.launcher.runner import parse_args


def test_hostfile_parse(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("# comment\nworker-0 slots=4\nworker-1 slots=4\n\n")
    pool = fetch_hostfile(str(hf))
    assert pool == {"worker-0": 4, "worker-1": 4}


def test_hostfile_bad_entry(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("worker-0 4\n")
    with pytest.raises(ValueError):
        fetch_hostfile(str(hf))


def test_missing_hostfile_is_empty():
    assert fetch_hostfile("/nonexistent/hostfile") == {}


def test_include_exclude():
    pool = {"a": 4, "b": 4, "c": 4}
    assert parse_inclusion_exclusion(pool, "a@b", "") == {"a": 4, "b": 4}
    assert parse_inclusion_exclusion(pool, "", "c") == {"a": 4, "b": 4}
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(pool, "zzz", "")


def test_parse_args_passthrough():
    args = parse_args(["--master_port", "9999", "train.py", "--lr", "0.1"])
    assert args.master_port == 9999
    assert args.user_script == "train.py"
    assert args.user_args == ["--lr", "0.1"]


# ---------------------------------------------------------------------------
# multi-node execution paths (round 2)
# ---------------------------------------------------------------------------
def test_ssh_runner_builds_per_host_commands():
    from deepspeed_tpu.launcher.runner import SshRunner

    r = SshRunner(["host-a", "host-b"], master="host-a", master_port=9999)
    cmds = r.build_cmds(["python", "train.py", "--x", "1"])
    assert len(cmds) == 2
    for rank, c in enumerate(cmds):
        assert c[0] == "ssh" and c[5] == ["host-a", "host-b"][rank]
        remote = c[6]
        assert "DS_TPU_NUM_PROCESSES=2" in remote
        assert f"DS_TPU_PROCESS_ID={rank}" in remote
        assert "DS_TPU_COORDINATOR=host-a" in remote
        assert "MASTER_PORT=9999" in remote
        assert remote.endswith("python train.py --x 1")


@pytest.mark.slow
def test_launcher_local_procs_end_to_end(tmp_path):
    """ds_tpu --num_local_procs 2: both workers join one rendezvous through
    comm.init_distributed and see the global device count."""
    import subprocess
    import sys

    script = tmp_path / "worker.py"
    script.write_text(
        "import jax\n"
        "import deepspeed_tpu.comm as dist\n"
        "dist.init_distributed()\n"
        "assert dist.get_world_size() == 2, dist.get_world_size()\n"
        "assert jax.device_count() == 4, jax.device_count()\n"
        "dist.barrier()\n"
        "print('LAUNCHED_OK', dist.get_rank())\n")
    import os as _os
    repo = _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    env = dict(_os.environ, PYTHONPATH=repo)
    rc = subprocess.call(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--num_local_procs", "2", "--local_devices_per_proc", "2",
         str(script)],
        env=env, cwd=repo, timeout=240)
    assert rc == 0


@pytest.mark.slow
def test_ds_bench_smoke(capsys):
    from deepspeed_tpu.launcher.ds_bench import run_sweep

    res = run_sweep(op="all_reduce", min_mb=1, max_mb=2, trials=2)
    assert len(res) == 2
    assert all(r["algbw_gbps"] > 0 for r in res)


@pytest.mark.slow
def test_launcher_kills_peers_when_one_worker_dies(tmp_path):
    """A crashing rank must not leave its peers hanging in a collective."""
    import subprocess
    import sys
    import time

    script = tmp_path / "crash.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['DS_TPU_PROCESS_ID'] == '1':\n"
        "    sys.exit(3)\n"
        "time.sleep(3600)\n")
    import os as _os
    repo = _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    t0 = time.time()
    rc = subprocess.call(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--num_local_procs", "2", "--local_devices_per_proc", "2",
         str(script)],
        env=dict(_os.environ, PYTHONPATH=repo), cwd=repo, timeout=120)
    assert rc == 3
    assert time.time() - t0 < 60  # did not wait for the sleeping peer


def test_ds_ssh_builds_per_host_commands(tmp_path, monkeypatch):
    """ds_tpu_ssh (reference bin/ds_ssh): one ssh per (filtered) host."""
    from deepspeed_tpu.launcher import ds_ssh

    hf = tmp_path / "hosts"
    hf.write_text("w0 slots=4\nw1 slots=4\nw2 slots=4\n")
    calls = []

    class FakeProc:
        returncode = 0

        def wait(self):
            return 0

    monkeypatch.setattr(ds_ssh.subprocess, "Popen",
                        lambda cmd: calls.append(cmd) or FakeProc())
    rc = ds_ssh.main(["-H", str(hf), "--exclude", "w1", "--", "echo", "hi"])
    assert rc == 0
    assert len(calls) == 2
    assert calls[0][-2:] == ["w0", "echo hi"]
    assert calls[1][-2:] == ["w2", "echo hi"]


def test_slurm_runner_builds_srun_command(tmp_path):
    """Slurm transport (reference multinode_runner.py:208 semantics on the TPU
    host model): one task per node, env via --export=ALL,K=V, include/exclude
    converted from '@' hostfile-filter syntax to slurm comma nodelists."""
    from deepspeed_tpu.launcher.multinode import SlurmRunner

    r = SlurmRunner(4, include="tpu-0@tpu-1", exclude="tpu-9", comment="ds",
                    exports={"DS_TPU_COORDINATOR": "tpu-0", "MASTER_PORT": "8476"},
                    launcher_args=["--partition", "tpu"])
    cmd = r.build_cmd("train.py", ["--epochs", "2"])
    assert cmd[:4] == ["srun", "-n", "4", "--ntasks-per-node=1"]
    assert ["--partition", "tpu"] == cmd[4:6]
    assert ["--comment", "ds"] == cmd[6:8]
    assert ["--nodelist", "tpu-0,tpu-1"] == cmd[8:10]
    assert ["--exclude", "tpu-9"] == cmd[10:12]
    assert cmd[12] == "--export=ALL,DS_TPU_COORDINATOR=tpu-0,MASTER_PORT=8476"
    import sys as _sys
    assert cmd[13:] == [_sys.executable, "-u", "train.py", "--epochs", "2"]


def test_openmpi_runner_builds_mpirun_command():
    """OpenMPI transport (reference multinode_runner.py:107 semantics): one
    process per node via --map-by ppr:1:node, env via -x K=V pairs."""
    from deepspeed_tpu.launcher.multinode import OpenMPIRunner

    r = OpenMPIRunner(2, hostfile="/tmp/hf",
                      exports={"DS_TPU_COORDINATOR": "h0"}, module=True)
    cmd = r.build_cmd("pkg.train", ["--lr", "1e-4"])
    assert cmd[:5] == ["mpirun", "-n", "2", "--map-by", "ppr:1:node"]
    assert ["-hostfile", "/tmp/hf"] == cmd[5:7]
    assert ["-x", "DS_TPU_COORDINATOR=h0"] == cmd[7:9]
    import sys as _sys
    assert cmd[9:] == [_sys.executable, "-u", "-m", "pkg.train", "--lr", "1e-4"]


def test_cli_builds_slurm_transport(tmp_path, monkeypatch):
    """ds_tpu --launcher slurm: hostfile -> host count, coordinator = first
    host, config forwarded; the built srun line is executed."""
    from deepspeed_tpu.launcher import runner as R

    hf = tmp_path / "hostfile"
    hf.write_text("tpu-1 slots=4\ntpu-0 slots=4\n")
    captured = {}

    def fake_run(self, user_script, user_args=()):
        captured["cmd"] = self.build_cmd(user_script, user_args)
        return 0

    monkeypatch.setattr("deepspeed_tpu.launcher.multinode._Transport.run",
                        fake_run)
    rc = R.main(["--hostfile", str(hf), "--launcher", "slurm",
                 "--deepspeed_config", "/tmp/ds.json", "train.py"])
    assert rc == 0
    cmd = captured["cmd"]
    assert cmd[:4] == ["srun", "-n", "2", "--ntasks-per-node=1"]
    # slurm is the one transport where hostfile order does NOT set rank
    # order: srun assigns SLURM_PROCID in Slurm's canonical (sorted) node
    # order regardless of --nodelist order, so the default coordinator must
    # be sorted()[0] (tpu-0) — the host that actually receives task 0
    assert ["--nodelist", "tpu-0,tpu-1"] == cmd[4:6]
    assert ("--export=ALL,DS_TPU_CONFIG=/tmp/ds.json,"
            "DS_TPU_COORDINATOR=tpu-0,MASTER_PORT=8476") in cmd


def test_cli_slurm_requires_hosts():
    from deepspeed_tpu.launcher import runner as R

    with pytest.raises(ValueError, match="hostfile or --num_nodes"):
        R.main(["--launcher", "openmpi", "train.py"])


def test_init_distributed_scheduler_env_fallback(tmp_path):
    """Under srun/mpirun the transports export only the coordinator address;
    rank/world must come from the scheduler's own env (SLURM_PROCID /
    OMPI_COMM_WORLD_RANK). Two processes numbered ONLY by SLURM vars must
    rendezvous."""
    import subprocess
    import sys

    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS','') + "
        "' --xla_force_host_platform_device_count=2').strip()\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import deepspeed_tpu.comm as dist\n"
        "dist.init_distributed()\n"
        "assert dist.get_world_size() == 2, dist.get_world_size()\n"
        "assert dist.get_rank() == int(os.environ['SLURM_PROCID'])\n"
        "dist.barrier()\n"
        "print('SLURM_ENV_OK', dist.get_rank())\n")
    import os as _os
    repo = _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    import socket
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close()
    procs = []
    for rank in range(2):
        env = dict(_os.environ, PYTHONPATH=repo,
                   SLURM_NTASKS="2", SLURM_PROCID=str(rank),
                   DS_TPU_COORDINATOR="127.0.0.1", MASTER_PORT=str(port))
        env.pop("DS_TPU_NUM_PROCESSES", None)
        env.pop("DS_TPU_PROCESS_ID", None)
        procs.append(subprocess.Popen([sys.executable, str(script)],
                                      env=env, cwd=repo))
    rcs = [p.wait(timeout=240) for p in procs]
    assert rcs == [0, 0], rcs


def test_cli_openmpi_writes_effective_hostfile(tmp_path, monkeypatch):
    """mpirun must see the filtered host set with one slot per host, not the
    raw user hostfile (which lists excluded hosts and chip-count slots)."""
    from deepspeed_tpu.launcher import runner as R

    hf = tmp_path / "hostfile"
    hf.write_text("tpu-0 slots=4\ntpu-1 slots=4\ntpu-2 slots=4\n")
    captured = {}

    def fake_run(self, user_script, user_args=()):
        captured["hostfile"] = self.hostfile
        captured["cmd"] = self.build_cmd(user_script, user_args)
        return 0

    monkeypatch.setattr("deepspeed_tpu.launcher.multinode._Transport.run",
                        fake_run)
    rc = R.main(["--hostfile", str(hf), "--exclude", "tpu-0",
                 "--launcher", "openmpi", "train.py"])
    assert rc == 0
    assert captured["cmd"][:5] == ["mpirun", "-n", "2", "--map-by", "ppr:1:node"]
    eff = open(captured["hostfile"]).read()
    assert eff == "tpu-1 slots=1\ntpu-2 slots=1\n"


def test_cli_ssh_missing_hostfile_raises():
    from deepspeed_tpu.launcher import runner as R

    with pytest.raises(ValueError, match="non-empty --hostfile"):
        R.main(["--launcher", "ssh", "/does/not/exist.py"])


def test_slurm_export_rejects_comma_values():
    from deepspeed_tpu.launcher.multinode import SlurmRunner

    r = SlurmRunner(2, exports={"DS_TPU_CONFIG": "/a,b/ds.json"})
    with pytest.raises(ValueError, match="commas"):
        r.build_cmd("train.py")


def test_init_distributed_ignores_bare_slurm_allocation(monkeypatch):
    """SLURM_NTASKS>1 WITHOUT a coordinator address (a plain `python train.py`
    inside an sbatch allocation) must stay single-process, not rendezvous."""
    import deepspeed_tpu.comm.comm as C

    monkeypatch.setattr(C, "_initialized", False)
    monkeypatch.setenv("SLURM_NTASKS", "8")
    monkeypatch.setenv("SLURM_PROCID", "0")
    for k in ("DS_TPU_NUM_PROCESSES", "DS_TPU_PROCESS_ID",
              "DS_TPU_COORDINATOR", "MASTER_ADDR"):
        monkeypatch.delenv(k, raising=False)
    called = {}
    monkeypatch.setattr(
        C.jax.distributed, "initialize",
        lambda **kw: called.setdefault("kw", kw))
    C.init_distributed()
    assert "kw" not in called  # single-process: no rendezvous attempted
    monkeypatch.setattr(C, "_initialized", False)


def test_init_distributed_explicit_world_requires_coordinator(monkeypatch):
    import deepspeed_tpu.comm.comm as C

    monkeypatch.setattr(C, "_initialized", False)
    monkeypatch.setenv("DS_TPU_NUM_PROCESSES", "2")
    for k in ("DS_TPU_COORDINATOR", "MASTER_ADDR"):
        monkeypatch.delenv(k, raising=False)
    with pytest.raises(RuntimeError, match="no coordinator"):
        C.init_distributed()
    monkeypatch.setattr(C, "_initialized", False)


def test_mpich_runner_builds_mpirun_command():
    """MPICH transport (reference multinode_runner.py:160 semantics): one
    process per node via -ppn 1, env via -genv K V pairs."""
    from deepspeed_tpu.launcher.multinode import MPICHRunner

    r = MPICHRunner(3, hostfile="/tmp/hf",
                    exports={"DS_TPU_COORDINATOR": "h0", "MASTER_PORT": "9"})
    cmd = r.build_cmd("train.py")
    assert cmd[:5] == ["mpirun", "-n", "3", "-ppn", "1"]
    assert ["-f", "/tmp/hf"] == cmd[5:7]
    assert ["-genv", "DS_TPU_COORDINATOR", "h0",
            "-genv", "MASTER_PORT", "9"] == cmd[7:13]
    import sys as _sys
    assert cmd[13:] == [_sys.executable, "-u", "train.py"]


def test_init_distributed_pmi_env_fallback(monkeypatch):
    """MPICH/Hydra export PMI_RANK/PMI_SIZE; with a coordinator set, rank and
    world size must come from them."""
    import deepspeed_tpu.comm.comm as C

    monkeypatch.setattr(C, "_initialized", False)
    monkeypatch.setenv("PMI_SIZE", "4")
    monkeypatch.setenv("PMI_RANK", "3")
    monkeypatch.setenv("DS_TPU_COORDINATOR", "h0")
    for k in ("DS_TPU_NUM_PROCESSES", "DS_TPU_PROCESS_ID", "RANK",
              "SLURM_NTASKS", "SLURM_PROCID", "OMPI_COMM_WORLD_SIZE",
              "OMPI_COMM_WORLD_RANK"):
        monkeypatch.delenv(k, raising=False)
    called = {}
    monkeypatch.setattr(C.jax.distributed, "initialize",
                        lambda **kw: called.update(kw))
    C.init_distributed()
    assert called["num_processes"] == 4 and called["process_id"] == 3
    monkeypatch.setattr(C, "_initialized", False)


def test_cli_mpich_writes_hydra_machinefile(tmp_path, monkeypatch):
    """Hydra machinefiles are 'host[:n]' lines, NOT OpenMPI's 'host slots=n'."""
    from deepspeed_tpu.launcher import runner as R

    hf = tmp_path / "hostfile"
    hf.write_text("tpu-0 slots=4\ntpu-1 slots=4\n")
    captured = {}

    def fake_run(self, user_script, user_args=()):
        captured["hostfile"] = self.hostfile
        return 0

    monkeypatch.setattr("deepspeed_tpu.launcher.multinode._Transport.run",
                        fake_run)
    rc = R.main(["--hostfile", str(hf), "--launcher", "mpich", "train.py"])
    assert rc == 0
    assert open(captured["hostfile"]).read() == "tpu-0\ntpu-1\n"


def test_pdsh_runner_builds_broadcast_command():
    """PDSH transport (reference multinode_runner.py:51 semantics): ONE command
    broadcast to every host via -w, rendezvous env inlined as exports, rank
    derived per-host from DS_TPU_HOSTS at init_distributed time."""
    from deepspeed_tpu.launcher.multinode import PDSHRunner

    r = PDSHRunner(["tpu-0", "tpu-1", "tpu-2"], master_port=9999,
                   exports={"XLA_FLAGS": "--foo"})
    cmd = r.build_cmd("train.py", ["--epochs", "2"])
    # -R ssh on pdsh's own argv (the rcmd module is chosen before any remote
    # shell runs, so an exported env var could never select it)
    assert cmd[:7] == ["pdsh", "-S", "-R", "ssh", "-f", "1024", "-w"]
    assert cmd[7] == "tpu-0,tpu-1,tpu-2"
    remote = cmd[8]
    assert "export DS_TPU_HOSTS=tpu-0,tpu-1,tpu-2;" in remote
    assert "export DS_TPU_NUM_PROCESSES=3;" in remote
    assert "export DS_TPU_COORDINATOR=tpu-0;" in remote
    assert "export MASTER_PORT=9999;" in remote
    assert "export XLA_FLAGS=--foo;" in remote
    assert remote.endswith("train.py --epochs 2")
    # no per-host rank in the broadcast command — that's the whole point
    assert "DS_TPU_PROCESS_ID" not in remote
    # the coordinator must be rank 0 (jax.distributed serves from process 0):
    # an explicit coordinator reorders the host list; an unlisted one raises
    r2 = PDSHRunner(["tpu-0", "tpu-1", "tpu-2"], coordinator="tpu-2")
    assert r2.hosts == ["tpu-2", "tpu-0", "tpu-1"]
    with pytest.raises(ValueError, match="not in the host list"):
        PDSHRunner(["tpu-0"], coordinator="elsewhere")


def test_pdsh_rank_from_hostname(monkeypatch):
    """The pdsh rank derivation: hostname position in DS_TPU_HOSTS, FQDN or
    short name; an unlisted host is an error, not rank 0."""
    import socket

    from deepspeed_tpu.comm.comm import _rank_from_hostlist

    monkeypatch.setattr(socket, "gethostname", lambda: "tpu-1.example.com")
    assert _rank_from_hostlist("tpu-0,tpu-1,tpu-2") == 1
    monkeypatch.setattr(socket, "gethostname", lambda: "tpu-2")
    assert _rank_from_hostlist("tpu-0, tpu-1, tpu-2") == 2
    # FQDN host list with a short local hostname (and vice versa) both match
    assert _rank_from_hostlist("tpu-0.cluster.internal,tpu-2.cluster.internal") == 1
    monkeypatch.setattr(socket, "gethostname", lambda: "other")
    try:
        _rank_from_hostlist("tpu-0,tpu-1")
        raise AssertionError("unlisted host must raise")
    except RuntimeError as e:
        assert "not in DS_TPU_HOSTS" in str(e)
    # ambiguous short names: a.dc1 and a.dc2 both match hostname 'a' — two
    # hosts deriving the same rank would hang jax.distributed init; refuse
    monkeypatch.setattr(socket, "gethostname", lambda: "a")
    with pytest.raises(RuntimeError, match="matches multiple"):
        _rank_from_hostlist("a.dc1,a.dc2")


def test_cli_builds_pdsh_transport(tmp_path, monkeypatch):
    """ds_tpu --launcher pdsh: hostfile -> ordered host list (rank order),
    coordinator = first host, config forwarded in the broadcast exports."""
    from deepspeed_tpu.launcher import runner as R

    hf = tmp_path / "hostfile"
    hf.write_text("tpu-1 slots=4\ntpu-0 slots=4\n")
    captured = {}

    def fake_run(self, user_script, user_args=()):
        captured["cmd"] = self.build_cmd(user_script, user_args)
        return 0

    monkeypatch.setattr("deepspeed_tpu.launcher.multinode._Transport.run",
                        fake_run)
    rc = R.main(["--hostfile", str(hf), "--launcher", "pdsh",
                 "--deepspeed_config", "/tmp/ds.json", "train.py"])
    assert rc == 0
    cmd = captured["cmd"]
    assert cmd[:7] == ["pdsh", "-S", "-R", "ssh", "-f", "1024", "-w"]
    # hostfile order, NOT lexicographic: rank order must match the hostfile
    # (reference multinode_runner convention — 'tpu-10' must not outrank
    # 'tpu-2' just because of string sort)
    assert cmd[7] == "tpu-1,tpu-0"
    assert "export DS_TPU_HOSTS=tpu-1,tpu-0;" in cmd[8]
    assert "export DS_TPU_COORDINATOR=tpu-1;" in cmd[8]
    assert "export DS_TPU_CONFIG=/tmp/ds.json;" in cmd[8]


def test_mvapich_runner_builds_mpirun_command():
    """MVAPICH transport (reference multinode_runner.py:256 semantics): one
    process per node via -ppn 1, env via -env K V, MV2 DL defaults kept."""
    from deepspeed_tpu.launcher.multinode import MVAPICHRunner

    r = MVAPICHRunner(2, hostfile="/tmp/hf",
                      exports={"DS_TPU_COORDINATOR": "h0"})
    cmd = r.build_cmd("train.py")
    assert cmd[:5] == ["mpirun", "-np", "2", "-ppn", "1"]
    assert ["--hostfile", "/tmp/hf"] == cmd[5:7]
    joined = " ".join(cmd)
    assert "-env DS_TPU_COORDINATOR h0" in joined
    assert "-env MV2_SUPPORT_DL 1" in joined
    assert "-env MV2_ENABLE_AFFINITY 0" in joined
    # user exports beat the MV2 defaults
    r2 = MVAPICHRunner(1, exports={"MV2_SUPPORT_DL": "0"})
    assert "-env MV2_SUPPORT_DL 0" in " ".join(r2.build_cmd("t.py"))
