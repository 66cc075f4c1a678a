"""Multi-host runtime: initialization, coordination barriers, host liveness.

This is the layer that breaks the single-process wall (ROADMAP item 1).
Everything above it — the ``sel`` mesh, the fused training engine, the
checkpointer — is already mesh-agnostic; what they need from here is small
and sharp:

  * ``initialize()`` — an idempotent, env-driven wrapper around
    ``jax.distributed.initialize``.  On the CPU backend it selects the gloo
    collectives implementation *before* initialization (the only point at
    which that config is writable), so two local CPU processes can run real
    cross-process ``psum``/``ppermute``/``all_gather`` — the CI smoke
    topology.  Launch N processes with::

        MILO_COORDINATOR=localhost:<port> MILO_NUM_PROCESSES=N \
            MILO_PROCESS_ID=<i> python ...

  * ``RuntimeBarrier`` — a named barrier over the jax coordination service
    (no device collectives, so it works outside any mesh/jit context).  A
    timeout means a peer did not arrive — the canonical dead-host signal —
    and is raised as ``HostLossError``, never a bare runtime error.
  * ``FileBarrier`` — the same contract over marker files, for in-process
    *simulated* multi-host tests (two ``CheckpointManager``s on threads).
    Marker files persist after the barrier passes, so names must be unique
    per rendezvous (the checkpointer's include the step); real runs use the
    coordination service, which has no such constraint.
  * ``HeartbeatWriter`` / ``HeartbeatMonitor`` — host liveness as fsync-free
    atomic JSON files on shared storage, with an injectable clock so
    staleness is testable without sleeping.  ``check()`` raises
    ``HostLossError`` naming the stale hosts; the restart then feeds the
    surviving host count into ``fault_tolerance.elastic_plan`` and resumes
    from the last *globally*-valid checkpoint.
  * ``global_put`` — place a host-replicated array onto a (possibly
    multi-process) mesh; every process fills its addressable shards from
    its own full copy, so no cross-host transfer happens at placement time.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.fault_tolerance import HostLossError

_HOST_RE = re.compile(r"^host_(\d+)\.json$")


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------

def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Idempotent ``jax.distributed.initialize`` with env-driven defaults.

    Reads ``MILO_COORDINATOR`` / ``MILO_NUM_PROCESSES`` / ``MILO_PROCESS_ID``
    when arguments are omitted; a no-op (returns False) when neither
    arguments nor env vars ask for multi-process execution, or when the
    runtime is already initialized.  On the CPU backend the gloo collectives
    implementation is selected first — cross-process collectives on CPU
    require it, and the flag is only writable before initialization.
    """
    if jax.distributed.is_initialized():
        return False
    coordinator_address = coordinator_address or os.environ.get("MILO_COORDINATOR")
    if num_processes is None:
        env_n = os.environ.get("MILO_NUM_PROCESSES")
        num_processes = int(env_n) if env_n else None
    if process_id is None:
        env_i = os.environ.get("MILO_PROCESS_ID")
        process_id = int(env_i) if env_i else None
    if coordinator_address is None or num_processes is None or num_processes < 2:
        return False
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    """Process 0 coordinates: it publishes global checkpoint manifests and
    owns garbage collection.  Single-process runs are their own coordinator."""
    return jax.process_index() == 0


# ---------------------------------------------------------------------------
# barriers
# ---------------------------------------------------------------------------

class RuntimeBarrier:
    """Named barrier over the jax coordination service.

    ``wait(name)`` blocks until every process has called ``wait`` with the
    same name; a timeout — the canonical "a peer died" observable — raises
    ``HostLossError``.  Requires ``initialize()`` to have run.
    """

    def __init__(self, timeout: float = 120.0):
        self.timeout = float(timeout)

    def wait(self, name: str) -> None:
        from jax._src import distributed as _dist

        client = _dist.global_state.client
        if client is None:
            raise RuntimeError(
                "RuntimeBarrier requires jax.distributed to be initialized "
                "(multihost.initialize())"
            )
        try:
            client.wait_at_barrier(name, timeout_in_ms=int(self.timeout * 1000))
        except jax.errors.JaxRuntimeError as e:
            raise HostLossError(
                f"barrier {name!r} not reached by all "
                f"{jax.process_count()} hosts within {self.timeout}s — "
                f"a peer is unreachable or dead ({e})"
            ) from e


@dataclasses.dataclass
class FileBarrier:
    """Marker-file barrier for in-process *simulated* multi-host tests.

    Each participant drops ``<root>/<name>.<index>`` and polls until all
    ``count`` markers exist.  Markers persist after the rendezvous, so every
    barrier name must be unique per logical rendezvous (the checkpointer's
    names embed the step number).  Real multi-process runs use
    ``RuntimeBarrier`` instead — the coordination service needs no shared
    filesystem semantics and cannot be confused by stale markers from a
    crashed earlier attempt.
    """

    root: str
    index: int
    count: int
    timeout: float = 30.0
    poll: float = 0.005
    clock: Callable[[], float] = time.monotonic
    sleep: Callable[[float], None] = time.sleep

    def wait(self, name: str) -> None:
        os.makedirs(self.root, exist_ok=True)
        mine = os.path.join(self.root, f"{name}.{self.index}")
        with open(mine, "w") as f:
            f.write(str(self.index))
        deadline = self.clock() + self.timeout
        while True:
            missing = [
                i for i in range(self.count)
                if not os.path.exists(os.path.join(self.root, f"{name}.{i}"))
            ]
            if not missing:
                return
            if self.clock() > deadline:
                raise HostLossError(
                    f"barrier {name!r}: hosts {missing} absent after "
                    f"{self.timeout}s",
                    hosts=missing,
                )
            self.sleep(self.poll)


def default_barrier(timeout: float = 120.0) -> RuntimeBarrier | None:
    """The barrier real multi-process runs coordinate on (None when this is
    a plain single-process run with no coordination service)."""
    return RuntimeBarrier(timeout) if jax.distributed.is_initialized() else None


# ---------------------------------------------------------------------------
# host liveness: heartbeat files with an injectable clock
# ---------------------------------------------------------------------------

class HeartbeatWriter:
    """Writes this host's liveness beacon: ``<dir>/host_<i>.json``.

    Atomic (temp file + rename) so a monitor never parses a torn beat; NOT
    fsync'd — a heartbeat is a freshness signal, not durable state, and an
    fsync per training step would be a straggler generator.
    """

    def __init__(
        self,
        directory: str,
        proc_index: int | None = None,
        *,
        clock: Callable[[], float] = time.time,
    ):
        self.directory = directory
        self.index = jax.process_index() if proc_index is None else proc_index
        self.clock = clock
        os.makedirs(directory, exist_ok=True)
        self.path = os.path.join(directory, f"host_{self.index}.json")

    def beat(self, step: int | None = None) -> None:
        payload = {"process_index": self.index, "time": self.clock()}
        if step is not None:
            payload["step"] = int(step)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, self.path)


class HeartbeatMonitor:
    """Reads every host's beacon and flags the stale/missing ones.

    ``expected`` hosts with no beacon file at all count as stale from the
    monitor's construction (age = now - created) — a host that never wrote a
    beat is indistinguishable from one that died before its first.  The
    injectable ``clock`` makes staleness a pure function of test inputs.
    """

    def __init__(
        self,
        directory: str,
        *,
        timeout: float = 60.0,
        expected: int | None = None,
        clock: Callable[[], float] = time.time,
    ):
        self.directory = directory
        self.timeout = float(timeout)
        self.expected = expected
        self.clock = clock
        self._created = clock()

    def _beats(self) -> dict[int, dict[str, Any]]:
        out: dict[int, dict[str, Any]] = {}
        try:
            names = os.listdir(self.directory)
        except FileNotFoundError:
            return out
        for fn in names:
            m = _HOST_RE.match(fn)
            if not m:
                continue
            try:
                with open(os.path.join(self.directory, fn)) as f:
                    out[int(m.group(1))] = json.load(f)
            except (json.JSONDecodeError, OSError):
                continue  # mid-replace read: treat as absent this poll
        return out

    def ages(self) -> dict[int, float]:
        """Seconds since each known/expected host's last beat."""
        now = self.clock()
        beats = self._beats()
        hosts = set(beats)
        if self.expected is not None:
            hosts |= set(range(self.expected))
        return {
            i: (now - beats[i]["time"]) if i in beats else (now - self._created)
            for i in sorted(hosts)
        }

    def stale_hosts(self) -> list[int]:
        return [i for i, age in self.ages().items() if age > self.timeout]

    def check(self) -> None:
        """Raise ``HostLossError`` naming every stale host."""
        stale = self.stale_hosts()
        if stale:
            ages = self.ages()
            detail = ", ".join(f"host {i}: {ages[i]:.1f}s" for i in stale)
            raise HostLossError(
                f"host(s) {stale} stale past the {self.timeout}s heartbeat "
                f"timeout ({detail}) — re-mesh via elastic_plan and resume "
                "from the last globally-valid checkpoint",
                hosts=stale,
            )

    def snapshot(self) -> dict[str, Any]:
        """JSON-safe liveness summary for ``MiloServer.health()``."""
        ages = self.ages()
        stale = [i for i, age in ages.items() if age > self.timeout]
        return {
            "expected": self.expected,
            "timeout": self.timeout,
            "ages": {str(i): round(age, 3) for i, age in ages.items()},
            "stale": stale,
        }


# ---------------------------------------------------------------------------
# global array placement
# ---------------------------------------------------------------------------

def mesh_spans_processes(mesh: Mesh) -> bool:
    """Whether the mesh's devices live in more than one process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def global_put(x: jax.Array, mesh: Mesh, spec: P) -> jax.Array:
    """Place a host-replicated array onto a (possibly multi-process) mesh.

    Every process holds the full ``x`` (replicated host data is the
    contract for selection inputs — each host loads/derives the same ground
    set) and fills only its *addressable* shards, so placement moves no
    bytes across hosts.  Works for sharded and replicated specs alike.
    """
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])
