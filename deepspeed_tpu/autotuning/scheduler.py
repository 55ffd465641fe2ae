"""Experiment scheduler (reference autotuning/scheduler.py:35
``ResourceManager``).

The reference fans experiment jobs out over a node pool, polls for
completion, and reads each experiment's metric file. The TPU-native
equivalent keeps the same lifecycle — queue experiments, run up to
``num_slots`` concurrently, collect a scalar metric per experiment — with
two runner styles:

* an in-process callable (``run_fn(exp) -> float``) — the default for
  single-host tuning where the engine is cheap to rebuild;
* a subprocess command template — the analogue of the reference launching
  ``deepspeed ...`` per experiment: each experiment gets a directory with
  its ``ds_config.json``; the command runs with DS_AUTOTUNING_EXP_DIR set
  and writes ``metric.json`` (``{"throughput": N}``) there.
"""

import json
import os
import subprocess
import threading
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger


class Experiment:
    def __init__(self, exp_id: int, config: Dict):
        self.exp_id = exp_id
        self.config = config
        self.metric: Optional[float] = None
        self.error: Optional[str] = None
        self.done = False
        self.host: Optional[str] = None   # node that ran it (pool mode)

    def __repr__(self):
        return (f"Experiment({self.exp_id}, metric={self.metric}, "
                f"done={self.done})")


class ResourceManager:
    def __init__(self,
                 run_fn: Optional[Callable[[Dict], float]] = None,
                 cmd_template: Optional[List[str]] = None,
                 exps_dir: str = "autotuning_exps",
                 num_slots: int = 1,
                 metric_key: str = "throughput",
                 timeout: float = 3600.0,
                 hosts: Optional[List[str]] = None,
                 ssh_cmd: Optional[List[str]] = None):
        """``hosts``: node pool for cross-host scheduling (reference
        scheduler.py:35 reads it from the hostfile): each host runs up to
        ``num_slots`` experiments concurrently, remote ones through
        ``ssh_cmd host`` with the experiment dir on a SHARED filesystem
        (the reference's same assumption). 'localhost'/'127.0.0.1' rows
        run without ssh, so a single-host pool needs no sshd."""
        assert (run_fn is None) != (cmd_template is None), (
            "pass exactly one of run_fn (in-process) or cmd_template "
            "(subprocess)")
        assert hosts is None or cmd_template is not None, (
            "cross-host scheduling needs cmd_template (run_fn is "
            "in-process and cannot hop hosts)")
        self.run_fn = run_fn
        self.cmd_template = cmd_template
        self.exps_dir = exps_dir
        self.num_slots = max(1, num_slots)
        self.metric_key = metric_key
        self.timeout = timeout
        self.hosts = list(hosts) if hosts else None
        self.ssh_cmd = list(ssh_cmd) if ssh_cmd else [
            "ssh", "-o", "StrictHostKeyChecking=no"]
        self.experiments: List[Experiment] = []

    def _build_remote_cmd(self, host: str, exp_dir: str) -> List[str]:
        """ssh wrapper for one experiment on ``host`` (reference
        scheduler.py run_job): cd into the launch cwd on the shared fs
        and re-export the experiment dir."""
        import shlex
        inner = " ".join(
            ["cd", shlex.quote(os.getcwd()), "&&", "env",
             f"DS_AUTOTUNING_EXP_DIR={shlex.quote(exp_dir)}"]
            + [shlex.quote(c) for c in self.cmd_template])
        return self.ssh_cmd + [host, inner]

    def schedule_experiments(self, configs: List[Dict]) -> List[Experiment]:
        start = len(self.experiments)
        exps = [Experiment(start + i, cfg) for i, cfg in enumerate(configs)]
        self.experiments.extend(exps)
        return exps

    # ------------------------------------------------------------- running
    def _run_subprocess(self, exp: Experiment,
                        host: Optional[str] = None) -> float:
        exp_dir = os.path.join(self.exps_dir, f"exp_{exp.exp_id}")
        os.makedirs(exp_dir, exist_ok=True)
        with open(os.path.join(exp_dir, "ds_config.json"), "w") as f:
            json.dump(exp.config, f, indent=2)
        if host is not None and host not in ("localhost", "127.0.0.1"):
            cmd = self._build_remote_cmd(host, exp_dir)
            env = dict(os.environ)
        else:
            cmd = self.cmd_template
            env = dict(os.environ, DS_AUTOTUNING_EXP_DIR=exp_dir)
        proc = subprocess.run(cmd, env=env,
                              capture_output=True, text=True,
                              timeout=self.timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"experiment {exp.exp_id} failed "
                f"(host={host or 'local'}, rc={proc.returncode}): "
                f"{proc.stderr[-2000:]}")
        with open(os.path.join(exp_dir, "metric.json")) as f:
            return float(json.load(f)[self.metric_key])

    def _worker(self, queue: List[Experiment], lock: threading.Lock,
                host: Optional[str] = None):
        while True:
            with lock:
                if not queue:
                    return
                exp = queue.pop(0)
            try:
                if self.run_fn is not None:
                    exp.metric = float(self.run_fn(exp.config))
                else:
                    exp.metric = self._run_subprocess(exp, host=host)
            except Exception as e:  # failed experiments stay metric=None
                exp.error = str(e)
                logger.warning(f"experiment {exp.exp_id} failed: {e}")
            exp.done = True
            exp.host = host

    def run(self) -> List[Experiment]:
        """Run all scheduled-but-not-done experiments; returns them."""
        todo = [e for e in self.experiments if not e.done]
        lock = threading.Lock()
        local = self.cmd_template is not None and any(
            h in ("localhost", "127.0.0.1")
            for h in (self.hosts or ["localhost"]))
        if local and todo:
            from deepspeed_tpu.utils.chip import holds_accelerator
            if holds_accelerator():
                # one process per chip: a child spawned on this machine
                # would fail or hang on the chips this parent holds
                raise RuntimeError(
                    "this process has initialised a jax accelerator "
                    "backend and holds its chips, so experiment "
                    "subprocesses on this machine cannot open them. Spawn "
                    "them from a parent that stays off jax (no "
                    "jax.device_count()/Autotuner.tune() before "
                    "ResourceManager.run), or run experiments in-process "
                    "with run_fn")
        if self.run_fn is not None and self.num_slots > 1:
            logger.warning(
                "in-process experiments share one device; forcing "
                "num_slots=1 (use cmd_template for parallel slots)")
        if self.hosts:
            # node pool: num_slots workers PER HOST, each pinned to its
            # host (reference ResourceManager node allocation)
            threads = [
                threading.Thread(target=self._worker,
                                 args=(todo, lock, host))
                for host in self.hosts for _ in range(self.num_slots)]
        else:
            slots = 1 if self.run_fn is not None else self.num_slots
            threads = [
                threading.Thread(target=self._worker, args=(todo, lock))
                for _ in range(min(slots, max(1, len(todo))))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return self.experiments

    def best(self) -> Optional[Experiment]:
        done = [e for e in self.experiments if e.metric is not None]
        return max(done, key=lambda e: e.metric) if done else None
