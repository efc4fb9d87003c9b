"""Node bootstrap: start/stop the head and agent processes.

Parity with the reference's node services (reference:
``python/ray/_private/node.py`` + ``services.py``): ``ray_tpu.init()`` on a
head node spawns the head control-plane process and a node agent, creates the
session directory tree (sockets/, logs/, store/), and connects the driver;
worker nodes spawn only an agent pointed at an existing head.
"""

from __future__ import annotations

import atexit
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Dict, Optional

from ray_tpu._private import lifecycle
from ray_tpu._private.config import CONFIG
from ray_tpu._private.ids import NodeID


def _detect_resources() -> Dict[str, float]:
    import psutil

    resources: Dict[str, float] = {
        "CPU": float(os.cpu_count() or 1),
        "memory": float(psutil.virtual_memory().total),
    }
    from ray_tpu._private.accelerators import get_all_accelerator_managers

    # every registered family probes; nonzero counts become schedulable
    # resources (reference: NodeManagerConfig.resource_config fed by the
    # AcceleratorManager ABC — TPU first-class, others detected the
    # same way so mixed-hardware clusters advertise what they have)
    for resource_name, manager in get_all_accelerator_managers().items():
        try:
            count = manager.get_current_node_num_accelerators()
        except Exception:
            count = 0
        if count:
            resources[resource_name] = float(count)
            for name, qty in \
                    manager.get_current_node_additional_resources().items():
                resources[name] = qty
    return resources


def default_session_root() -> str:
    base = "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir()
    return os.path.join(base, "ray_tpu")


class Node:
    """Manages the subprocesses backing one node of the cluster."""

    def __init__(
        self,
        head: bool = True,
        head_host: str = "127.0.0.1",
        head_port: int = 0,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        object_store_memory: Optional[int] = None,
        session_dir: Optional[str] = None,
        node_name: str = "",
    ):
        self.is_head = head
        self.node_id = NodeID.from_random().hex()
        self.head_host = head_host
        self.head_port = head_port
        if session_dir is None:
            session_name = f"session_{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:8]}"
            session_dir = os.path.join(default_session_root(), session_name)
        self.session_dir = session_dir
        os.makedirs(os.path.join(session_dir, "sockets"), exist_ok=True)
        os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
        self.store_dir = os.path.join(session_dir, "store", self.node_id[:12])
        os.makedirs(self.store_dir, exist_ok=True)
        merged = _detect_resources()
        if resources:
            merged.update(resources)
        self.resources = merged
        self.labels = dict(labels or {})
        if node_name:
            self.labels["node_name"] = node_name
        self.object_store_memory = object_store_memory
        self.head_proc: Optional[subprocess.Popen] = None
        self.agent_proc: Optional[subprocess.Popen] = None
        self.agent_unix_path = ""
        self.agent_tcp_port = 0

    # ------------------------------------------------------------------ up
    def start(self) -> None:
        if self.is_head:
            self._start_head()
        self._start_agent()

    def _subprocess_env(self) -> dict:
        """Control-plane processes (head/agent) never touch jax and stay
        off the accelerator (config.keep_off_accelerator). The lifecycle
        variables tie the daemon to this session's registry and fate-share
        it with this (spawning) process."""
        from ray_tpu._private.config import keep_off_accelerator

        env = keep_off_accelerator(dict(os.environ))
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        env["RAY_TPU_PARENT_PID"] = str(os.getpid())
        return env

    def _start_head(self) -> None:
        log = open(os.path.join(self.session_dir, "logs", "head.log"), "ab")
        self.head_proc = subprocess.Popen(
            [
                sys.executable, "-m", "ray_tpu._private.gcs",
                "--session-dir", self.session_dir,
                "--port", str(self.head_port),
            ],
            env=self._subprocess_env(),
            stdout=log,
            stderr=log,
            start_new_session=True,
        )
        log.close()
        lifecycle.register_process(self.session_dir, "gcs",
                                   self.head_proc.pid, self.node_id)
        port_file = os.path.join(self.session_dir, "head_port")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if os.path.exists(port_file):
                with open(port_file) as f:
                    content = f.read().strip()
                if content:
                    self.head_port = int(content)
                    return
            if self.head_proc.poll() is not None:
                raise RuntimeError(
                    "head process exited during startup; see "
                    f"{self.session_dir}/logs/head.log"
                )
            time.sleep(CONFIG.node_boot_poll_s)
        raise TimeoutError("head process did not report its port")

    def _start_agent(self) -> None:
        ready_file = os.path.join(
            self.session_dir, f"agent-ready-{self.node_id[:12]}.json"
        )
        log = open(
            os.path.join(self.session_dir, "logs", f"agent-{self.node_id[:12]}.log"),
            "ab",
        )
        self.agent_proc = subprocess.Popen(
            [
                sys.executable, "-m", "ray_tpu._private.agent",
                "--node-id", self.node_id,
                "--session-dir", self.session_dir,
                "--store-dir", self.store_dir,
                "--head-host", self.head_host,
                "--head-port", str(self.head_port),
                "--resources", json.dumps(self.resources),
                "--labels", json.dumps(self.labels),
                "--object-store-memory", str(self.object_store_memory or 0),
                "--ready-file", ready_file,
            ],
            env=self._subprocess_env(),
            stdout=log,
            stderr=log,
            start_new_session=True,
        )
        log.close()
        lifecycle.register_process(self.session_dir, "agent",
                                   self.agent_proc.pid, self.node_id)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if os.path.exists(ready_file):
                try:
                    with open(ready_file) as f:
                        info = json.load(f)
                    self.agent_unix_path = info["unix_path"]
                    self.agent_tcp_port = info["tcp_port"]
                    return
                except (json.JSONDecodeError, KeyError):
                    pass
            if self.agent_proc.poll() is not None:
                raise RuntimeError(
                    "agent process exited during startup; see "
                    f"{self.session_dir}/logs/agent-{self.node_id[:12]}.log"
                )
            time.sleep(CONFIG.node_boot_poll_s)
        raise TimeoutError("agent did not become ready")

    # ---------------------------------------------------------------- down
    def stop(self, cleanup_session: bool = False) -> None:
        """Stop this node's daemons, then walk the session pid registry,
        and return when both are gone from the process table.

        The direct SIGTERM gives the agent its graceful window (it kills
        its own workers, waits for them, and then its forkserver, on
        SIGTERM: `lifecycle.AGENT_TEARDOWN_GRACE_S`); the registry sweep
        then catches anything that escaped its spawner's process group —
        forkserver grandchildren setsid into foreign pgids, so signalling
        ``head_proc``/``agent_proc`` groups alone leaks them — and waits
        for what an agent cut short has handed to pid 1.
        ``cleanup_session`` sweeps the WHOLE session (every node) and
        unlinks the dir with its shm segments; otherwise only this node's
        registered processes are reaped (a worker node leaving a shared
        session must not take the cluster down).
        """
        lifecycle.terminate_tree(
            [self.agent_proc, self.head_proc],
            sigterm_timeout_s=lifecycle.AGENT_TEARDOWN_GRACE_S)
        try:
            lifecycle.reap_session(
                self.session_dir,
                node_id=None if cleanup_session else self.node_id,
                remove=cleanup_session)
        except Exception:
            if cleanup_session:
                shutil.rmtree(self.session_dir, ignore_errors=True)
