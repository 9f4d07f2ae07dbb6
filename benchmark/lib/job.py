"""One real `edl train` job: launch, poll, stop, reap.

The benchmark's own small copy of what `tools/elastic_drill.run_drill`
does around a job (PERF.md lists the original for a later PR to fold).
The job is `python -m elasticdl_tpu.client.main train ...
--instance_backend local_process`: the master runs in that process, the
worker and the PS shards are its children, and only the worker opens the
chip. Nothing here imports jax.
"""

import glob
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

ROLE_MODULES = {
    "worker": ("elasticdl_tpu.worker.main", "--worker_id"),
    "ps": ("elasticdl_tpu.ps.main", "--ps_id"),
}

# The job's sitecustomize: every python process of the job keeps a file
# saying whether it has initialised a jax backend. A thread rewrites it
# twice a second, because roles end by SIGTERM or SIGKILL as often as by
# returning, and atexit sees neither.
_ROLE_HOOK = '''
import atexit, json, os, sys, threading, time

_seen = [False]  # sticky: a worker drops its backends before it exits

def _report():
    # Never import here: this thread must not race the role's own imports.
    bridge = sys.modules.get("jax._src.xla_bridge")
    probe = getattr(bridge, "backends_are_initialized", None)
    up = _seen[0] = _seen[0] or bool(probe and probe())
    path = os.path.join(os.environ["EDL_BENCH_ROLES"], "%d.json" % os.getpid())
    with open(path + ".tmp", "w") as f:
        json.dump({"role": os.environ.get("ELASTICDL_ROLE", "master"),
                   "backend_initialized": up}, f)
    os.replace(path + ".tmp", path)

def _loop():
    while True:
        time.sleep(0.5)
        _report()

if os.environ.get("EDL_BENCH_ROLES"):
    atexit.register(_report)
    threading.Thread(target=_loop, daemon=True).start()
'''


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pgrep(module):
    out = subprocess.run(
        ["pgrep", "-af", module], capture_output=True, text=True
    ).stdout
    return [line for line in out.splitlines() if line.strip()]


class Job:
    """A launched job. `workdir` receives job.log, obs/ (events, metrics
    endpoints), roles/ (who opened a backend) and, in a traced run, the
    program's own profile directory."""

    def __init__(self, repo, workdir, train_args, env=None):
        self.repo = repo
        self.workdir = workdir
        self.port = free_port()
        self.obs_dir = os.path.join(workdir, "obs")
        self.roles_dir = os.path.join(workdir, "roles")
        self.log_path = os.path.join(workdir, "job.log")
        hook = os.path.join(workdir, "hook")
        for d in (self.obs_dir, self.roles_dir, hook):
            os.makedirs(d, exist_ok=True)
        with open(os.path.join(hook, "sitecustomize.py"), "w") as f:
            f.write(_ROLE_HOOK)
        full_env = dict(os.environ)
        full_env.update({
            "PYTHONPATH": f"{hook}:{repo}",
            "EDL_BENCH_ROLES": self.roles_dir,
            "ELASTICDL_OBS_DIR": self.obs_dir,
        })
        full_env.update(env or {})
        self.argv = [
            sys.executable, "-m", "elasticdl_tpu.client.main", "train",
            "--instance_backend", "local_process",
            "--master_port", str(self.port),
            *train_args,
        ]
        # A file, never a pipe: nobody reads while the poller polls.
        self._log = open(self.log_path, "w")
        self.t_launch = time.time()
        self.proc = subprocess.Popen(
            self.argv, stdout=self._log, stderr=subprocess.STDOUT,
            env=full_env, cwd=repo,
            start_new_session=True,  # own process group: reaped whole
        )
        self._stub = None

    # ---------- the master's status RPC (what `edl top` reads) ----------

    def _connect(self):
        from elasticdl_tpu.common import rpc

        self._stub = rpc.Stub(
            rpc.build_channel(f"127.0.0.1:{self.port}", ready_timeout=0),
            rpc.MASTER_SERVICE,
        )

    def status(self):
        """One JobStatusResponse, or None while the master is not (or no
        longer) answering."""
        import grpc

        from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

        if self._stub is None:
            # Not before the port answers: a refused first call would
            # open the client's circuit breaker for seconds.
            try:
                socket.create_connection(
                    ("127.0.0.1", self.port), timeout=0.2).close()
            except OSError:
                return None
            self._connect()
        try:
            return self._stub.get_job_status(
                pb.GetJobStatusRequest(), timeout=5
            )
        except grpc.RpcError:
            return None

    def alive(self):
        return self.proc.poll() is None

    # ---------- the job's processes ----------

    def role_pids(self):
        needle = f"--master_addr 127.0.0.1:{self.port}"
        pids = []
        for module, _ in ROLE_MODULES.values():
            for line in _pgrep(module):
                if needle in line:
                    pids.append(int(line.split()[0]))
        return pids

    # ---------- what the roles publish ----------

    def scrape(self, role):
        """{series line name -> value} of one role's /metrics endpoint,
        or {} when it is not up."""
        path = os.path.join(self.obs_dir, "endpoints", f"{role}.json")
        try:
            with open(path) as f:
                port = json.load(f)["port"]
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2
            ).read().decode()
        except (OSError, ValueError, KeyError):
            return {}
        out = {}
        for m in re.finditer(
            r"^([A-Za-z_:][\w:]*(?:\{[^}]*\})?) ([-+0-9.eE]+|nan|inf)$",
            body, re.M,
        ):
            out[m.group(1)] = float(m.group(2))
        return out

    def events(self):
        out = []
        for path in sorted(
            glob.glob(os.path.join(self.obs_dir, "events.jsonl*")),
            reverse=True,  # events.jsonl.1 is the older generation
        ):
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            pass  # a line cut by SIGKILL
        out.sort(key=lambda e: e.get("ts", 0.0))
        return out

    def log_text(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def backends_by_role(self):
        """{role: did any of its processes initialise a jax backend}."""
        out = {}
        for path in glob.glob(os.path.join(self.roles_dir, "*.json")):
            try:
                with open(path) as f:
                    rec = json.load(f)
            except ValueError:
                continue
            out[rec["role"]] = (
                out.get(rec["role"], False) or rec["backend_initialized"]
            )
        return out

    # ---------- the end ----------

    def wait(self, timeout):
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None

    def stop(self, grace=10.0, patience=60.0):
        """Cancel the job the way a user does (SIGTERM to `edl train`),
        then reap its whole process group and wait until every role is
        really gone: a killed worker takes seconds to let go of its chips
        (3 to 6 s on one chip), and the reference needs them next.
        Returns the pids still there after `patience` (should be none)."""
        roles = self.role_pids()
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            self.wait(grace)
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.wait(10)
        roles = set(roles) | set(self.role_pids())
        deadline = time.time() + patience
        while time.time() < deadline:
            left = [p for p in roles if os.path.exists(f"/proc/{p}")]
            if not left:
                break
            for pid in left:
                try:
                    os.kill(pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
            time.sleep(0.1)
        if not self._log.closed:
            self._log.close()
        return left
