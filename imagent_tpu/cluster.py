"""Distributed runtime init: Slurm env parsing, coordinator resolution, mesh.

TPU-native replacement for the reference's L2 layer (``imagenet.py:224-274``):

* The reference parses ``SLURM_*`` env vars into ranks (``imagenet.py:225-234``),
  resolves the master host by forking ``scontrol show hostnames``
  (``imagenet.py:237-238``), exports ``MASTER_ADDR/PORT/WORLD_SIZE/RANK``
  (``imagenet.py:241-244``) and calls
  ``init_process_group('env://', 'nccl')`` (``imagenet.py:270-273``).
* Here the same contract collapses into a pure, unit-testable Slurm parser
  (no subprocess: the nodelist grammar is expanded in Python, with
  ``scontrol`` only as a fallback) plus one call to
  ``jax.distributed.initialize()`` — the PJRT coordination service is the
  rendezvous; XLA compiles collectives onto ICI/DCN, so the NCCL tuning
  block (``imagenet.sh:19-23``) has no analogue.

Mesh design: a 2-D ``(data, model)`` mesh. The parity workload uses only the
``data`` axis (the reference is pure DP, SURVEY §2c), but the ``model`` axis
is first-class so tensor/sequence-parallel shardings slot in without
re-architecting.
"""

from __future__ import annotations

import dataclasses
import os
import re
import subprocess
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
PIPE_AXIS = "pipe"
MODEL_AXIS = "model"
DEFAULT_COORDINATOR_PORT = 29500  # reference's MASTER_PORT (imagenet.py:242)


@dataclasses.dataclass(frozen=True)
class SlurmEnv:
    """Rank geometry derived from Slurm, mirroring ``imagenet.py:225-234``."""

    n_nodes: int
    node_id: int
    local_rank: int
    global_rank: int
    world_size: int
    coordinator: str  # first hostname of SLURM_JOB_NODELIST
    # Elastic pod (``--elastic``, imagent_tpu/elastic.py): after a
    # rendezvous, ``global_rank``/``world_size``/``coordinator`` hold
    # the ACTIVE session geometry (what jax.distributed was initialized
    # with) and these carry the launched identity: the scheduler slot
    # this process was started as (heartbeat/tombstone identity, stable
    # across resizes), the committed roster's members (launched ranks),
    # and the roster attempt. 0/-1/() on the non-elastic path.
    launched_world: int = 0
    launched_rank: int = -1
    elastic_attempt: int = 0
    members: tuple = ()

    @property
    def is_coordinator(self) -> bool:
        return self.global_rank == 0


def expand_nodelist(nodelist: str) -> list[str]:
    """Expand a Slurm nodelist expression into hostnames, in pure Python.

    Handles the common grammar: ``ener[021-030]``, ``n[1,3,5-7]b``,
    comma-separated groups. Equivalent to ``scontrol show hostnames``
    (which the reference forks at ``imagenet.py:237-238``) for these forms.
    """
    hosts: list[str] = []
    # Split on commas that are not inside brackets.
    parts, depth, cur = [], 0, []
    for ch in nodelist:
        if ch == "[":
            depth += 1
        elif ch == "]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))

    for part in parts:
        m = re.match(r"^([^\[]*)\[([^\]]+)\](.*)$", part)
        if not m:
            hosts.append(part)
            continue
        prefix, body, suffix = m.groups()
        for item in body.split(","):
            if "-" in item:
                lo, hi = item.split("-")
                width = len(lo)
                for i in range(int(lo), int(hi) + 1):
                    hosts.append(f"{prefix}{i:0{width}d}{suffix}")
            else:
                hosts.append(f"{prefix}{item}{suffix}")
    return hosts


def resolve_coordinator(nodelist: str) -> str:
    """First host of the nodelist — the reference's ``scontrol`` master
    resolution (``imagenet.py:237-238``) without the subprocess.

    The ``scontrol`` fallback retries with jittered backoff: at job
    start every task of a large step hits the controller at once, and a
    briefly-overloaded slurmctld answering one fork with a timeout must
    not kill the whole pod's rendezvous."""
    try:
        hosts = expand_nodelist(nodelist)
        if hosts:
            return hosts[0]
    except (ValueError, IndexError):
        pass
    # Fallback: ask scontrol like the reference does.
    from imagent_tpu.resilience.retry import retry_call

    out = retry_call(
        subprocess.run,
        ["scontrol", "show", "hostnames", nodelist],
        capture_output=True, text=True, check=True,
        attempts=4, base_delay=0.2, max_delay=5.0,
        retry_on=(subprocess.CalledProcessError, OSError),
        describe=f"scontrol show hostnames {nodelist}",
    ).stdout
    return out.split()[0]


def parse_slurm_env(env: Mapping[str, str]) -> SlurmEnv | None:
    """Pure function: Slurm env dict → rank geometry, or None outside Slurm.

    Contract matches ``imagenet.py:225-234``: NNODES/NODEID/LOCALID/PROCID/
    NTASKS (+ JOB_NODELIST for the coordinator). Unit-testable with a fake
    dict per SURVEY §4 ("Multi-host logic").
    """
    if "SLURM_JOB_NUM_NODES" not in env and "SLURM_NNODES" not in env:
        return None
    n_nodes = int(env.get("SLURM_JOB_NUM_NODES", env.get("SLURM_NNODES", "1")))
    node_id = int(env.get("SLURM_NODEID", "0"))
    local_rank = int(env.get("SLURM_LOCALID", "0"))
    global_rank = int(env.get("SLURM_PROCID", "0"))
    world_size = int(env.get("SLURM_NTASKS", str(n_nodes)))
    nodelist = env.get("SLURM_JOB_NODELIST", env.get("SLURM_NODELIST", ""))
    coordinator = resolve_coordinator(nodelist) if nodelist else "127.0.0.1"
    return SlurmEnv(
        n_nodes=n_nodes,
        node_id=node_id,
        local_rank=local_rank,
        global_rank=global_rank,
        world_size=world_size,
        coordinator=coordinator,
    )


def canonical_backend(backend: str | None) -> str | None:
    """Operator-compat mapping for the reference's flag values
    (``imagenet.py:440``, invoked as ``--backend=nccl`` at
    ``imagenet.sh:26``): nccl = "the accelerator fabric" -> TPU
    runtime; gloo = "CPU fallback" -> cpu."""
    return {"nccl": "tpu", "gloo": "cpu"}.get(backend, backend)


def require_backend(backend: str | None) -> None:
    """The platform JAX actually initialized must be the one that was
    asked for. ``--backend=tpu`` with no chip attached used to carry on
    silently on the CPU; now it is a fatal-config refusal (ValueError
    -> exit 78) that names what was found. Initializes the backend."""
    backend = canonical_backend(backend)
    if not backend:
        return
    found = jax.devices()[0]
    if found.platform != backend:
        raise ValueError(
            f"--backend={backend} was requested but JAX initialized the "
            f"{found.platform!r} platform ({found.device_kind} x"
            f"{jax.device_count()}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}). Refusing to "
            f"run on a platform that was not asked for: attach the "
            f"{backend} device, or pass --backend={found.platform} to "
            "run there on purpose.")


def initialize(backend: str | None = None,
               env: Mapping[str, str] | None = None,
               port: int | None = None,
               elastic_dir: str | None = None,
               elastic_settle: float = 10.0,
               group_size: int = 1) -> SlurmEnv | None:
    """Initialize the distributed runtime.

    Replaces ``imagenet.py:237-273``: under Slurm with >1 task, call
    ``jax.distributed.initialize(coordinator, num_processes, process_id)``
    (PJRT coordination service); single-process runs skip it. ``backend``
    selects the PJRT platform (the reference's ``--backend nccl`` analogue,
    ``imagenet.py:440``).

    ``elastic_dir`` (``--elastic``): before touching jax.distributed,
    run the filesystem rendezvous (``imagent_tpu/elastic.py``) — the
    processes that actually showed up commit a roster, and THAT decides
    ``(num_processes, process_id, coordinator, port)``: a pod that lost
    a host re-forms at world N-1 on a fresh coordinator port instead of
    timing out against the scheduler's stale geometry; a full relaunch
    with the replacement present re-expands to N the same way. The
    returned ``SlurmEnv`` then carries both the active and the launched
    geometry (see the dataclass). Raises
    ``exitcodes.ElasticExcludedError`` when the roster committed
    without this host.
    """
    backend = canonical_backend(backend)
    if backend and backend != "tpu":
        # Force the requested platform: "cpu"/"gpu" must win even over
        # an environment-preset JAX_PLATFORMS — both in this process
        # (jax.config) and in spawned workers (env var). "tpu" forces
        # nothing here (JAX selects the TPU itself wherever one is
        # attached); ``require_backend`` then REFUSES whatever else the
        # runtime fell back to, so --backend=tpu can never quietly
        # train on the CPU.
        os.environ["JAX_PLATFORMS"] = backend
        jax.config.update("jax_platforms", backend)
    environ = env if env is not None else os.environ
    senv = parse_slurm_env(environ)
    if senv is not None and senv.world_size > 1:
        if backend == "cpu" or environ.get("JAX_PLATFORMS",
                                           "").startswith("cpu"):
            # Cross-process computations on the CPU backend (the pod
            # dryruns and mp_* drills) need a CPU collectives
            # implementation — without gloo every cross-host psum/
            # allgather dies with "Multiprocess computations aren't
            # implemented on the CPU backend". Must be set before the
            # backend initializes; harmless for single-process runs
            # (guarded by world_size above).
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        if port is None:
            # Two jobs sharing a login host must not collide on the
            # fixed reference port (MASTER_PORT 29500, imagenet.py:242).
            raw = environ.get("IMAGENT_COORDINATOR_PORT", "")
            try:
                port = (int(raw.strip()) if raw.strip()
                        else DEFAULT_COORDINATOR_PORT)
            except ValueError:
                raise ValueError(
                    f"IMAGENT_COORDINATOR_PORT={raw!r} is not a port "
                    "number") from None
        if elastic_dir is not None:
            from imagent_tpu import elastic as elastic_lib
            ros = elastic_lib.rendezvous(
                elastic_dir, senv.global_rank, senv.world_size, port,
                settle_secs=elastic_settle, group_size=group_size)
            members = [int(r) for r in ros["members"]]
            active_rank = members.index(senv.global_rank)
            senv = dataclasses.replace(
                senv,
                launched_world=senv.world_size,
                launched_rank=senv.global_rank,
                world_size=len(members), global_rank=active_rank,
                coordinator=str(ros["coordinator"]),
                elastic_attempt=int(ros["attempt"]),
                members=tuple(members))
            if len(members) > 1:
                jax.distributed.initialize(
                    coordinator_address=(f"{ros['coordinator']}:"
                                         f"{int(ros['port'])}"),
                    num_processes=len(members),
                    process_id=active_rank,
                )
            else:
                # Shrunk all the way to one host: no distributed
                # runtime — the gloo CPU collectives armed above would
                # demand a distributed client at backend init, so
                # un-arm them (single-process psums are local).  The
                # flag's off value is the STRING "none" — Python None
                # is rejected by make_cpu_client ("Unknown collectives
                # implementation None"), which turned every shrink-to-
                # one restart into a backend-init crash (exit 70).
                jax.config.update(
                    "jax_cpu_collectives_implementation", "none")
            return senv
        jax.distributed.initialize(
            coordinator_address=f"{senv.coordinator}:{port}",
            num_processes=senv.world_size,
            process_id=senv.global_rank,
        )
    return senv


def rank_banner(senv: SlurmEnv | None) -> str:
    """The per-rank init banner the reference prints (``imagenet.py:252-262``,
    visible interleaved at ``imagent_sgd.out:1-272``)."""
    if senv is None:
        return (f"[proc {jax.process_index()}/{jax.process_count()}] "
                f"devices={jax.local_device_count()} (no Slurm env)")
    elastic = ""
    if senv.launched_world and senv.launched_world != senv.world_size:
        elastic = (f" ELASTIC (launched slot {senv.launched_rank}/"
                   f"{senv.launched_world}, roster attempt "
                   f"{senv.elastic_attempt})")
    return (
        f"[rank {senv.global_rank}/{senv.world_size}] "
        f"node {senv.node_id}/{senv.n_nodes} local_rank {senv.local_rank} "
        f"coordinator {senv.coordinator} "
        f"local_devices={jax.local_device_count()}" + elastic
    )


def make_mesh(model_parallel: int = 1,
              devices: Sequence[jax.Device] | None = None,
              pipeline_parallel: int = 1) -> Mesh:
    """Build the global 3-D ``(data, pipe, model)`` device mesh.

    Lays the model axis innermost so its collectives (tensor/sequence
    parallel psum, all-to-all) ride the fastest ICI links; the pipe axis
    sits next (single-hop ``ppermute`` per tick); the data axis spans the
    remaining chips (the reference's 16-rank DP world, ``imagenet.py:316``).
    Unused axes have size 1, so pure-DP shardings are unchanged.
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    per_replica = model_parallel * pipeline_parallel
    if devs.size % per_replica:
        raise ValueError(
            f"device count {devs.size} not divisible by model_parallel"
            f"={model_parallel} x pipeline_parallel={pipeline_parallel}")
    shape = (devs.size // per_replica, pipeline_parallel, model_parallel)
    if devices is None:
        # Topology-aware assignment: on real pods this places the inner
        # (model, pipe) axes on physically adjacent chips so their
        # collectives take single ICI hops; correctness never depends on
        # the order (batch rows may land on any device), only locality.
        from jax.experimental import mesh_utils
        try:
            return Mesh(mesh_utils.create_device_mesh(shape),
                        (DATA_AXIS, PIPE_AXIS, MODEL_AXIS))
        except (ValueError, AssertionError) as e:
            # Unusual topology: fall through to the naive order — and
            # say so, because the inner axes may now span slow links.
            if jax.process_index() == 0:
                print(f"WARNING: topology-aware device mesh unavailable "
                      f"for shape {shape} ({type(e).__name__}: {e}); "
                      "using the naive jax.devices() order — "
                      "model/pipe-axis collectives may not ride "
                      "adjacent chips", flush=True)
    grid = devs.reshape(shape)
    return Mesh(grid, (DATA_AXIS, PIPE_AXIS, MODEL_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for input batches: split batch dim over ``data``."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for replicated state (params/opt state in pure DP)."""
    return NamedSharding(mesh, P())
