"""The ``ds_tpu`` CLI launcher.

TPU-native equivalent of the reference's ``deepspeed`` CLI
(``bin/deepspeed`` -> ``launcher/runner.py:376 main`` -> per-node
``launcher/launch.py:216``). On GPU clusters the launcher forks one process per
device and wires NCCL rendezvous env; on TPU the unit is one process per *host*
(all local chips belong to it), so:

- single host: exec the script in-process-count-1 mode (JAX sees all local chips);
- multi-host pods: each host runs the same command (GKE/`gcloud compute tpus
  tpu-vm ssh --worker=all`); this launcher sets the rendezvous env
  (``DS_TPU_COORDINATOR``/``DS_TPU_NUM_PROCESSES``/``DS_TPU_PROCESS_ID``) that
  ``deepspeed_tpu.comm.init_distributed`` consumes, from flags or TPU metadata.

Hostfile / --include / --exclude filters are parsed with the reference's syntax so
existing job scripts port.
"""

import argparse
import os
import re
import subprocess
import sys

from ..utils.logging import logger


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        description="DeepSpeed-TPU launcher", usage="ds_tpu [options] script.py [script args]"
    )
    parser.add_argument("--hostfile", type=str, default="",
                        help="hostfile (reference syntax: '<host> slots=<n>')")
    parser.add_argument("--include", type=str, default="",
                        help="hosts to include, e.g. 'worker-0@worker-1'")
    parser.add_argument("--exclude", type=str, default="",
                        help="hosts to exclude")
    parser.add_argument("--num_nodes", type=int, default=-1)
    parser.add_argument("--master_addr", type=str, default="")
    parser.add_argument("--master_port", type=int, default=8476)
    parser.add_argument("--node_rank", type=int, default=-1,
                        help="this host's index in the pod (auto from TPU metadata if unset)")
    parser.add_argument("--num_local_procs", type=int, default=0,
                        help="spawn N local worker processes (multi-host "
                             "simulated on this machine; CPU pods / tests)")
    parser.add_argument("--local_devices_per_proc", type=int, default=0,
                        help="with --num_local_procs: virtual CPU devices per "
                             "worker (required: local workers run on the CPU "
                             "platform — an accelerator belongs to one "
                             "process)")
    parser.add_argument("--ssh", action="store_true",
                        help="with --hostfile: launch the command on every "
                             "host over ssh (reference PDSH runner role)")
    parser.add_argument("--ssh_port", type=int, default=22)
    parser.add_argument("--launcher", type=str, default="",
                        choices=["", "ssh", "pdsh", "slurm", "openmpi",
                                 "mpich", "mvapich"],
                        help="multi-node transport (reference --launcher): "
                             "ssh | pdsh | slurm (srun) | openmpi | mpich "
                             "(mpirun); one process per HOST either way")
    parser.add_argument("--launcher_args", type=str, default="",
                        help="extra args passed through to srun/mpirun")
    parser.add_argument("--slurm_comment", type=str, default="",
                        help="slurm --comment (reference --comment flag)")
    parser.add_argument("--deepspeed_config", type=str, default=None)
    parser.add_argument("--module", action="store_true",
                        help="run the target as 'python -m <module>'")
    parser.add_argument("user_script", type=str, help="training script")
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


def fetch_hostfile(path):
    """Reference ``launcher/runner.py:188``: '<hostname> slots=<n>' lines."""
    if not path or not os.path.isfile(path):
        return {}
    resource_pool = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                hostname, slots = line.split()
                _, slot_count = slots.split("=")
                resource_pool[hostname] = int(slot_count)
            except ValueError:
                raise ValueError(f"Hostfile contains a bad entry: {line!r}")
    return resource_pool


def parse_inclusion_exclusion(resource_pool, inclusion, exclusion):
    """Reference ``launcher/runner.py:243`` filter syntax: 'host1@host2'."""
    active = dict(resource_pool)
    if inclusion:
        wanted = set(inclusion.split("@"))
        unknown = wanted - set(active)
        if unknown:
            raise ValueError(f"--include hosts not in hostfile: {sorted(unknown)}")
        active = {h: s for h, s in active.items() if h in wanted}
    if exclusion:
        banned = set(exclusion.split("@"))
        unknown = banned - set(active)
        if unknown:
            raise ValueError(f"--exclude hosts not in hostfile: {sorted(unknown)}")
        active = {h: s for h, s in active.items() if h not in banned}
    return active


class SshRunner:
    """Multi-node command builder+executor over plain ssh — the reference's
    ``multinode_runner.py`` PDSH role (``:51``) without the pdsh dependency:
    one ssh per host, rendezvous env inlined into the remote command."""

    def __init__(self, hosts, master, master_port, ssh_port=22):
        self.hosts = list(hosts)
        self.master = master
        self.master_port = master_port
        self.ssh_port = ssh_port

    def build_cmds(self, cmd, extra_env=None):
        import shlex

        cmds = []
        for rank, host in enumerate(self.hosts):
            env = {
                "DS_TPU_NUM_PROCESSES": str(len(self.hosts)),
                "DS_TPU_COORDINATOR": self.master,
                "DS_TPU_PROCESS_ID": str(rank),
                "MASTER_PORT": str(self.master_port),
            }
            env.update(extra_env or {})
            exports = " ".join(f"{k}={shlex.quote(str(v))}"
                               for k, v in sorted(env.items()))
            remote = (f"cd {shlex.quote(os.getcwd())} && {exports} "
                      f"{' '.join(shlex.quote(c) for c in cmd)}")
            cmds.append(["ssh", "-p", str(self.ssh_port),
                         "-o", "StrictHostKeyChecking=no", host, remote])
        return cmds

    def run(self, cmd, extra_env=None):
        procs = [subprocess.Popen(c) for c in self.build_cmds(cmd, extra_env)]
        return _wait_kill_on_failure(procs)


def launch_local_procs(cmd, num_procs, env, devices_per_proc,
                       master_port=None):
    """Spawn ``num_procs`` local workers with the rendezvous env — multi-host
    simulated on one machine (the reference test-harness pattern,
    ``tests/unit/common.py:183``), also the real path for CPU pods. Every
    worker is pinned to the CPU platform with ``devices_per_proc`` virtual
    devices: N workers inheriting the host's accelerator would all try to
    own it, and a chip belongs to one process at a time."""
    import socket

    if devices_per_proc < 1:
        raise ValueError(
            "--num_local_procs needs --local_devices_per_proc N (virtual CPU "
            "devices per worker): local workers cannot share the host's "
            "accelerator — one process drives all local chips")
    if master_port is None:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        master_port = s.getsockname()[1]
        s.close()
    procs = []
    for rank in range(num_procs):
        wenv = dict(env)
        wenv.update({
            "DS_TPU_NUM_PROCESSES": str(num_procs),
            "DS_TPU_COORDINATOR": "127.0.0.1",
            "DS_TPU_PROCESS_ID": str(rank),
            "MASTER_PORT": str(master_port),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                wenv.get("XLA_FLAGS", "")) +
                f" --xla_force_host_platform_device_count="
                f"{devices_per_proc}").strip(),
        })
        procs.append(subprocess.Popen(cmd, env=wenv))
    return _wait_kill_on_failure(procs)


def _wait_kill_on_failure(procs, poll_s=0.5):
    """Wait for all workers, but terminate the rest as soon as one fails —
    a dead rank leaves its peers blocked in a collective forever (XLA has no
    collective timeout; the reference's launch.py kills siblings the same
    way, ``launcher/launch.py:119``)."""
    import time

    try:
        while True:
            rcs = [p.poll() for p in procs]
            if all(rc is not None for rc in rcs):
                return max(rcs) if rcs else 0
            if any(rc not in (None, 0) for rc in rcs):
                bad = next(i for i, rc in enumerate(rcs) if rc not in (None, 0))
                logger.error(
                    f"worker {bad} exited rc={rcs[bad]}; terminating the rest")
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                deadline = time.time() + 10
                for p in procs:
                    while p.poll() is None and time.time() < deadline:
                        time.sleep(0.1)
                    if p.poll() is None:
                        p.kill()
                return rcs[bad]
            time.sleep(poll_s)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def main(args=None):
    args = parse_args(args)

    env = os.environ.copy()
    resource_pool = fetch_hostfile(args.hostfile)
    if resource_pool:
        resource_pool = parse_inclusion_exclusion(resource_pool, args.include, args.exclude)
        hosts = list(resource_pool)
        num_nodes = len(hosts) if args.num_nodes < 0 else args.num_nodes
        master = args.master_addr or hosts[0]
        node_rank = args.node_rank
        if node_rank < 0:
            # FQDN/short matching in either direction (the same rule as
            # comm._rank_from_hostlist) — an exact-string lookup silently
            # gave every host rank 0 when the hostfile spelled FQDNs but
            # gethostname() returned short names
            from ..comm.comm import _rank_from_hostlist

            try:
                node_rank = _rank_from_hostlist(",".join(hosts))
            except RuntimeError as e:
                if "matches multiple" in str(e):
                    raise  # duplicate ranks would hang jax.distributed init
                node_rank = 0  # launching from a non-worker host
        env["DS_TPU_NUM_PROCESSES"] = str(num_nodes)
        env["DS_TPU_COORDINATOR"] = master
        env["DS_TPU_PROCESS_ID"] = str(node_rank)
        env["MASTER_PORT"] = str(args.master_port)
        logger.info(
            f"ds_tpu: pod launch — {num_nodes} hosts, coordinator {master}:"
            f"{args.master_port}, this host rank {node_rank}"
        )
    else:
        logger.info("ds_tpu: single-host launch (all local TPU chips)")

    if args.deepspeed_config:
        env["DS_TPU_CONFIG"] = args.deepspeed_config

    if args.module:
        cmd = [sys.executable, "-m", args.user_script] + args.user_args
    else:
        cmd = [sys.executable, args.user_script] + args.user_args

    if args.num_local_procs > 0:
        logger.info(f"ds_tpu: spawning {args.num_local_procs} local workers")
        return launch_local_procs(cmd, args.num_local_procs, env,
                                  devices_per_proc=args.local_devices_per_proc,
                                  master_port=None)
    if args.ssh and not args.launcher:
        args.launcher = "ssh"
    if args.launcher == "ssh" and not resource_pool:
        raise ValueError("--launcher ssh needs a non-empty --hostfile "
                         "(a missing path silently resolves to no hosts)")
    if args.launcher == "ssh":
        hosts = list(resource_pool)
        runner = SshRunner(hosts, args.master_addr or hosts[0],
                           args.master_port, ssh_port=args.ssh_port)
        extra = {"DS_TPU_CONFIG": args.deepspeed_config} \
            if args.deepspeed_config else None
        logger.info(f"ds_tpu: ssh launch on {len(hosts)} hosts")
        return runner.run(cmd, extra)
    if args.launcher == "pdsh":
        import shlex

        from .multinode import PDSHRunner

        if not resource_pool:
            raise ValueError("--launcher pdsh needs --hostfile")
        hosts = list(resource_pool)  # hostfile order = rank order (reference multinode_runner semantics)
        exports = {}
        if args.deepspeed_config:
            exports["DS_TPU_CONFIG"] = args.deepspeed_config
        runner = PDSHRunner(
            hosts, coordinator=args.master_addr or hosts[0],
            master_port=args.master_port, exports=exports,
            launcher_args=shlex.split(args.launcher_args), module=args.module)
        if not runner.backend_exists():
            logger.warning("ds_tpu: pdsh not found on PATH; the built "
                           "command may fail to execute")
        logger.info(f"ds_tpu: pdsh launch on {len(hosts)} hosts")
        return runner.run(args.user_script, args.user_args)
    if args.launcher in ("slurm", "openmpi", "mpich", "mvapich"):
        import shlex

        from .multinode import MULTINODE_RUNNERS

        # one process per host: hostfile slots are chips, which all belong to
        # the host process — the host count is what srun/mpirun see
        if resource_pool:
            num_hosts = len(resource_pool)
        elif args.num_nodes > 0:
            num_hosts = args.num_nodes
        else:
            raise ValueError(
                f"--launcher {args.launcher} needs --hostfile or --num_nodes")
        if not args.master_addr and not resource_pool:
            raise ValueError(
                f"--launcher {args.launcher} needs --master_addr when no "
                f"hostfile is given (the coordinator must be one of the hosts)")
        master = args.master_addr or list(resource_pool)[0]
        if args.launcher == "slurm" and resource_pool and not args.master_addr:
            # srun assigns SLURM_PROCID in Slurm's canonical (sorted) node
            # order, NOT --nodelist order — the default coordinator must be
            # the host that receives task 0, or every rank dials a host where
            # no jax.distributed coordinator listens
            master = sorted(resource_pool)[0]
        exports = {"DS_TPU_COORDINATOR": master,
                   "MASTER_PORT": str(args.master_port)}
        if args.deepspeed_config:
            exports["DS_TPU_CONFIG"] = args.deepspeed_config
        kw = dict(exports=exports,
                  launcher_args=shlex.split(args.launcher_args),
                  module=args.module)
        if args.launcher == "slurm":
            if resource_pool:
                # pin srun to the (already include/exclude-filtered) hostfile
                # hosts — otherwise the allocation may place no task on the
                # exported coordinator and every rank hangs at rendezvous.
                # Sorted: matches Slurm's canonical task-distribution order
                # (nodelist order is not honored by srun)
                kw.update(include="@".join(sorted(resource_pool)))
            else:
                kw.update(include=args.include, exclude=args.exclude)
            kw.update(comment=args.slurm_comment)
        else:
            if resource_pool:
                # hand mpirun the EFFECTIVE host set (filters applied, one
                # process per host), not the raw user hostfile — the raw file
                # still contains excluded hosts and chip-count slots. Each
                # flavor gets its own machinefile dialect: OpenMPI reads
                # "host slots=n", Hydra (MPICH) reads "host[:n]".
                import tempfile

                line = ("{h} slots=1\n" if args.launcher == "openmpi"
                        else "{h}\n")  # mpich/mvapich: plain host lines
                eff = tempfile.NamedTemporaryFile(
                    "w", prefix="ds_tpu_hosts_", suffix=".txt", delete=False)
                for h in resource_pool:
                    eff.write(line.format(h=h))
                eff.close()
                kw.update(hostfile=eff.name)
            else:
                kw.update(hostfile="")
        runner = MULTINODE_RUNNERS[args.launcher](num_hosts, **kw)
        if not runner.backend_exists():
            logger.warning(
                f"ds_tpu: {args.launcher} tooling not found on PATH; the "
                f"built command may fail to execute")
        logger.info(f"ds_tpu: {args.launcher} launch on {num_hosts} hosts")
        return runner.run(args.user_script, args.user_args)
    result = subprocess.call(cmd, env=env)
    return result


if __name__ == "__main__":
    sys.exit(main())
