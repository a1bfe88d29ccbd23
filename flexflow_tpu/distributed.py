"""Multi-host entry point: the GASNet/Legion-transport analog.

The reference scales across nodes by building Legion with GASNet
(USE_GASNET=1, nmt/Makefile:24; `-d` flag README.md:38-41) and launching
one rank per node; Legion/Realm then move region data over the wire.  The
TPU-native equivalent is `jax.distributed` + GSPMD: every host runs THE
SAME program, `initialize()` connects them, and `jax.devices()` then spans
every chip in the slice/pod — after which the entire framework works
unchanged (a MachineModel over the global device list; XLA emits ICI
collectives within a slice and DCN collectives across slices from exactly
the same sharding annotations).

    # on every host (e.g. via gcloud alpha compute tpus tpu-vm ssh --worker=all)
    from flexflow_tpu import distributed
    machine = distributed.initialize()          # TPU pods: auto-detected
    ff = build_inception_v3(cfg, machine)       # unchanged from 1 chip

There is no per-op communication code anywhere to port — SURVEY.md §2.7:
communication is derived, not written.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from flexflow_tpu.machine import MachineModel, Topology

# did THIS process bring up a jax.distributed client?  release()/rejoin
# consult it so single-process runs never touch the coordinator.
# _RELEASE_LOCK makes release() idempotent AND re-entrant: fit()'s drain
# path and its error path can both reach it (possibly from a signal
# handler interrupting the other caller), and exactly one of them may
# run the actual shutdown.
import threading as _threading

_STATE = {"initialized": False}
_RELEASE_LOCK = _threading.RLock()


def is_initialized() -> bool:
    """True when this process initialized (and still holds) the
    jax.distributed client."""
    return _STATE["initialized"]


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None,
               topology: Optional[Topology] = None,
               coordinator_timeout_s: Optional[float] = None,
               connect_attempts: int = 1) -> MachineModel:
    """Connect this process to the cluster and return the global machine.

    On Cloud TPU all arguments are auto-detected from the metadata server;
    elsewhere pass coordinator_address ("host:port" of process 0),
    num_processes, and process_id.  Single-process (the common dev case)
    skips `jax.distributed` entirely and is a no-op wrapper around
    ``MachineModel()``.

    The returned MachineModel spans every device of every process, with a
    two-tier Topology (ICI inside a slice = this host's local device
    count per group by default; DCN across) feeding the strategy-search
    cost model.

    Coordinator-timeout handling (elastic round): the explicit path
    passes ``coordinator_timeout_s`` through to jax.distributed's
    ``initialization_timeout`` (where the installed jax supports it) and
    retries a timed-out connection up to ``connect_attempts`` times with
    bounded deterministic backoff (utils/retry.py) — a respawned host
    arriving before its coordinator is a normal event under ``--elastic``
    restarts, not an error."""
    import os

    import jax

    explicit = (coordinator_address is not None
                or (num_processes or 0) > 1 or process_id is not None)
    # env markers Cloud TPU sets on multi-host slices — the zero-arg
    # auto-detect path only fires there, so single-process dev boxes
    # (CPU tests, single-host chips) never touch jax.distributed
    auto = any(m in os.environ for m in (
        "TPU_WORKER_HOSTNAMES", "CLOUD_TPU_TASK_ID",
        "MEGASCALE_COORDINATOR_ADDRESS", "TPU_PROCESS_ADDRESSES"))
    if explicit:
        def _connect():
            kwargs = dict(coordinator_address=coordinator_address,
                          num_processes=num_processes,
                          process_id=process_id,
                          local_device_ids=local_device_ids)
            if coordinator_timeout_s is not None:
                kwargs["initialization_timeout"] = \
                    int(coordinator_timeout_s)
            jax.distributed.initialize(**kwargs)
            _STATE["initialized"] = True

        def _connect_once():
            try:
                _connect()
            except RuntimeError as e:
                # second initialize() in the same process: keep the
                # existing client (jax.distributed is one-shot; use
                # shutdown() before reconfiguring).  A TIMEOUT is
                # retryable; anything else (bad coordinator, mismatched
                # process count) must surface, not silently degrade to a
                # single-host world.
                msg = str(e).lower()
                if "already initialized" in msg:
                    _STATE["initialized"] = True
                    return
                if "timeout" in msg or "timed out" in msg \
                        or "deadline" in msg:
                    raise TimeoutError(str(e)) from e
                raise

        if max(int(connect_attempts), 1) > 1:
            from flexflow_tpu.utils.retry import (RetryPolicy,
                                                  call_with_retry)

            call_with_retry(
                _connect_once,
                policy=RetryPolicy(attempts=max(int(connect_attempts), 1),
                                   base_delay=1.0, max_delay=10.0),
                retry_on=(TimeoutError,))
        else:
            _connect_once()
    elif auto:
        try:
            jax.distributed.initialize()  # args metadata-auto-detected
            _STATE["initialized"] = True
        except (RuntimeError, ValueError):
            # backend already initialized (dev sessions that imported jax
            # first) or metadata incomplete (RuntimeError / ValueError
            # 'coordinator_address should be defined'): stay
            # single-process — the env markers alone are not proof of a
            # usable cluster
            pass
    multiprocess = jax.process_count() > 1
    devices = jax.devices()
    if topology is None and multiprocess:
        # ICI inside each host's slice, DCN across — feed the two-tier
        # cost model accordingly (single-process keeps MachineModel's
        # own all-ICI default)
        topology = Topology(
            devices_per_ici_group=max(len(jax.local_devices()), 1))
    return MachineModel(devices=devices, topology=topology)


def shutdown() -> None:
    """Tear down the jax.distributed client (idempotent)."""
    import jax

    with _RELEASE_LOCK:
        _STATE["initialized"] = False
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


def release() -> bool:
    """Coordinator cleanup: tear down the client IF this process brought
    one up, no-op otherwise.  ``fit()`` calls this on every error exit
    AND at the end of a graceful drain, so a departing host releases the
    coordinator (and its barrier slot) promptly instead of holding the
    other hosts until their timeout.  Idempotent and re-entrant — both
    paths may call it, in any order, and only the first performs the
    shutdown.  Returns True when this call did the teardown."""
    with _RELEASE_LOCK:
        if not _STATE["initialized"]:
            return False
        _STATE["initialized"] = False
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    return True


def elastic_rejoin(ckpt_dir: str,
                   coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None,
                   model=None,
                   topology: Optional[Topology] = None,
                   coordinator_timeout_s: float = 60.0,
                   connect_attempts: int = 5,
                   olog=None, log=print) -> Tuple[MachineModel, int,
                                                  Optional[dict],
                                                  Optional[dict],
                                                  Optional[dict]]:
    """The ``--elastic`` restart protocol for a RESPAWNED host.

    A host that crashed (or was preempted) and came back cannot splice
    into the surviving mesh mid-step — collectives are compiled against a
    fixed device set.  Instead it: (1) tears down any stale client and
    re-initializes against the coordinator, retrying connection timeouts
    with bounded backoff (every surviving host must reach the SAME
    restart barrier, which the orchestrator triggers by restarting them
    with identical flags); (2) loads the newest VERIFIED checkpoint from
    ``ckpt_dir`` (the async writer keeps one recent — a respawn costs at
    most one checkpoint interval); (3) returns the fresh global machine
    plus the restored ``(step, params, state, opt_state)`` so the driver
    rebuilds its model on the rejoined mesh and resumes.

    With ``model`` given, restored leaves land on the model's shardings
    (same contract as ``restore_checkpoint``).  ``model`` may also be a
    FACTORY ``machine -> model``: a respawned process cannot build its
    model before rejoining (the global machine does not exist until
    ``initialize`` returns, and jax forbids re-initializing after the
    backend is live), so the factory is called with the rejoined
    machine and the restore places onto the freshly built model.  When
    no checkpoint exists yet, returns step 0 with None trees (a restart
    before the first save simply begins again)."""
    from flexflow_tpu.utils import checkpoint as ckpt

    shutdown()
    machine = initialize(coordinator_address=coordinator_address,
                         num_processes=num_processes,
                         process_id=process_id, topology=topology,
                         coordinator_timeout_s=coordinator_timeout_s,
                         connect_attempts=connect_attempts)
    if model is not None and callable(model) \
            and not hasattr(model, "layers"):
        model = model(machine)
    step, params, state, opt_state = 0, None, None, None
    if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        step, params, state, opt_state = ckpt.restore_checkpoint(
            ckpt_dir, model, olog=olog)
        log(f"elastic rejoin: restored verified checkpoint step {step} "
            f"from {ckpt_dir!r} on a "
            f"{machine.num_devices}-device mesh")
    else:
        log(f"elastic rejoin: no checkpoint under {ckpt_dir!r}; "
            f"rejoining from step 0")
    if olog is not None and getattr(olog, "enabled", False):
        olog.event("elastic_rejoin", step=step, dir=ckpt_dir,
                   devices=machine.num_devices)
    return machine, step, params, state, opt_state
