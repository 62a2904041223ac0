"""Device mesh helpers.

The reference initializes a process-global Network singleton from a machine
list (network.cpp:17-30, linkers_socket.cpp). The TPU equivalent is a
`jax.sharding.Mesh` over the visible devices; multi-host pods join via
`jax.distributed.initialize` (DCN) before constructing the mesh — the
moral analog of the reference's `Network::Init`.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_mesh", "default_mesh", "init_distributed",
           "provision_virtual_devices", "setup_multihost"]


def provision_virtual_devices(n_devices: int) -> None:
    """Force an n-device virtual CPU backend (the reference's no-cluster
    distributed testing, _test_distributed.py:54-135, is N localhost
    processes; ours is N virtual XLA host devices).

    Must run BEFORE the first backend touch: once any jax.devices() call
    initializes a backend, the CPU device count is latched for the process.
    jax is usually imported already by then, which latches the
    environment variables — the jax.config updates are what actually
    take effect. This permanently switches the process (and, via
    os.environ, subprocesses) to the CPU platform; it is a one-shot
    test/dryrun provision, not a runtime mode toggle.
    """
    try:
        from jax._src import xla_bridge as _xb
        already_up = _xb.backends_are_initialized()
    except Exception:
        # Private API moved: attempt the config mutations below —
        # jax_num_cpu_devices raises its own clear error post-init, and
        # succeeds pre-init, so provisioning still works either way.
        already_up = False
    if already_up:
        if len(jax.devices()) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices but the JAX backend was already "
                f"initialized with {len(jax.devices())}; call "
                f"provision_virtual_devices before any other JAX use")
        return
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={n_devices}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_num_cpu_devices", n_devices)
    jax.config.update("jax_platforms", "cpu")
    # verify the provision actually took: when the initialized-backend
    # detection above is unavailable (private API moved) and some
    # harness touched JAX first, the config mutations silently miss the
    # already-latched backend — the resulting single-device mesh errors
    # would surface far away, in shard_map. Touching jax.devices() here
    # latches the backend we just configured, which the very next call
    # (make_mesh) does anyway.
    got = len(jax.devices())
    if got < n_devices:
        raise RuntimeError(
            f"provision_virtual_devices({n_devices}) had no effect: the "
            f"JAX backend is up with {got} device(s). The CPU device "
            f"count latches at first backend use — call "
            f"provision_virtual_devices before any other JAX use "
            f"(imports are fine; jax.devices()/jit/device_put are not).")


def make_mesh(num_devices: int = 0, axis: str = "data") -> Mesh:
    devices = jax.devices()
    if num_devices <= 0:
        num_devices = len(devices)
    if num_devices > len(devices):
        raise ValueError(
            f"requested {num_devices} devices, only {len(devices)} visible")
    return Mesh(np.array(devices[:num_devices]), (axis,))


def default_mesh(axis: str = "data") -> Mesh:
    return make_mesh(0, axis)


def _enable_cpu_collectives() -> None:
    """Cross-process computations on the CPU backend need a real
    collectives implementation — with the default ("none") every
    multi-process jit/allgather fails with "Multiprocess computations
    aren't implemented on the CPU backend". jaxlib ships gloo; select
    it before the backend initializes. Only applies when the process
    is pinned to CPU (multi-process CPU tests, the chaos harness);
    TPU runs keep the default ICI/DCN transport."""
    plat = os.environ.get("JAX_PLATFORMS") or jax.config.jax_platforms or ""
    if "cpu" in plat:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host initialization (reference Network::Init + machine list;
    here jax.distributed handles rendezvous over DCN)."""
    if coordinator_address is not None:
        _enable_cpu_collectives()
        jax.distributed.initialize(coordinator_address, num_processes,
                                   process_id)


def _local_addresses() -> set:
    import socket
    addrs = {"127.0.0.1", "localhost", "0.0.0.0"}
    try:
        host = socket.gethostname()
        addrs.add(host)
        for ip in socket.gethostbyname_ex(host)[2]:
            addrs.add(ip)
    except OSError:
        pass
    return addrs


def setup_multihost(num_machines: int, machines: str = "",
                    machine_list_filename: str = "",
                    local_listen_port: int = 12400) -> None:
    """Join a multi-machine training group from the reference's network
    config surface (config.h: machines / machine_list_filename /
    local_listen_port / num_machines; Network::Init + linkers_socket.cpp
    machine-list parsing). The TPU equivalent is a jax.distributed
    rendezvous over DCN: machine 0's entry is the coordinator, each
    process finds its rank by matching its local addresses + listen port
    in the list (override with env LIGHTGBM_TPU_MACHINE_RANK). After
    this, jax.devices() is the GLOBAL device set and the mesh/shard_map
    collectives span all hosts."""
    import os

    # NOTE: jax.process_count() would itself initialize the backend;
    # consult the distributed client state directly instead
    try:
        from jax._src.distributed import global_state as _dstate
        if _dstate.client is not None:
            # rendezvous already done (e.g. by the launcher). A stale
            # rendezvous that doesn't match THIS machine list would make
            # collectives hang or span wrong ranks — verify, don't trust.
            want_rank = os.environ.get("LIGHTGBM_TPU_MACHINE_RANK")
            got_n = getattr(_dstate, "num_processes", None)
            got_rank = getattr(_dstate, "process_id", None)
            if got_n is not None and got_n != num_machines:
                raise RuntimeError(
                    f"a jax.distributed rendezvous already exists with "
                    f"{got_n} processes, but num_machines={num_machines} "
                    f"was requested. Re-fitting with a different machine "
                    f"set requires fresh worker processes (the JAX "
                    f"rendezvous is once-per-process, like the "
                    f"reference's Network::Init socket ring).")
            if (want_rank is not None and got_rank is not None
                    and int(want_rank) != got_rank):
                raise RuntimeError(
                    f"existing rendezvous has rank {got_rank} but "
                    f"LIGHTGBM_TPU_MACHINE_RANK={want_rank}; restart the "
                    f"worker processes to change machine ranks.")
            return
    except ImportError:
        pass
    try:
        from jax._src import xla_bridge as _xb
        if _xb.backends_are_initialized():
            raise RuntimeError(
                "multi-machine setup must run before any JAX backend use "
                "(the reference calls Network::Init before loading data, "
                "application.cpp:165). Call "
                "lightgbm_tpu.setup_multihost(...) at program start, "
                "before constructing Datasets or Boosters.")
    except ImportError:
        pass
    entries = []
    if machines:
        for item in machines.split(","):
            item = item.strip()
            if not item:
                continue
            host, _, port = item.rpartition(":")
            entries.append((host, int(port)))
    elif machine_list_filename:
        with open(machine_list_filename) as fh:
            for line in fh:
                parts = line.split()
                if len(parts) >= 2:
                    entries.append((parts[0], int(parts[1])))
    if not entries:
        raise ValueError(
            "num_machines > 1 requires `machines` (host:port,...) or "
            "machine_list_filename (reference config.h machine list)")
    if len(entries) != num_machines:
        raise ValueError(
            f"machine list has {len(entries)} entries but "
            f"num_machines={num_machines}")
    rank_env = os.environ.get("LIGHTGBM_TPU_MACHINE_RANK")
    if rank_env is not None:
        rank = int(rank_env)
    else:
        local = _local_addresses()
        matches = [i for i, (h, p) in enumerate(entries)
                   if h in local and p == local_listen_port]
        if len(matches) != 1:
            raise ValueError(
                "could not determine this machine's rank from the "
                "machine list (matched %d entries); set "
                "LIGHTGBM_TPU_MACHINE_RANK" % len(matches))
        rank = matches[0]
    coordinator = f"{entries[0][0]}:{entries[0][1]}"
    _enable_cpu_collectives()
    jax.distributed.initialize(coordinator, num_machines, rank)
    _seed_membership_epoch(num_machines)


def _seed_membership_epoch(world: int) -> None:
    """Adopt the membership epoch a reincarnating supervisor handed us
    (LIGHTGBM_TPU_EPOCH, written when an elastic shrink committed) so
    the very first guarded collective of the new world already carries
    the agreed epoch — a straggler resumed from the OLD membership
    record diverges on that gather and is rejected instead of silently
    exchanging rows sharded for the wrong world."""
    epoch_env = os.environ.get("LIGHTGBM_TPU_EPOCH")
    try:
        from ..distributed.elastic import set_epoch
        if epoch_env is not None:
            set_epoch(int(epoch_env))
        from ..observability.registry import registry
        registry.record_membership(
            int(epoch_env) if epoch_env is not None else 0, world)
    except Exception:   # pragma: no cover - forensics must not block init
        pass
