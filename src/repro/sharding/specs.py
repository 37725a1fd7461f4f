"""Logical-axis sharding rules (MaxText-style) + device routing helpers.

Models annotate tensors with *logical* axis names; this module maps them to
mesh axes per :data:`RULES`, dropping any mapping whose divisibility fails
(a replicated axis is always correct, never wrong — the roofline analysis
then shows the cost and the perf loop fixes the layout, e.g. by head
padding).

Mesh axes:
    pod    — inter-pod data parallelism (multi-pod mesh only)
    data   — intra-pod data parallelism + FSDP (ZeRO-3) param sharding
    model  — tensor parallelism (heads / mlp / vocab / experts / kv-seq)

Public helpers
--------------
``mesh_context(mesh)`` — scope the active mesh (thread-local); every
``constrain``/``make_pspec`` call inside resolves logical names against it::

    mesh = make_mesh((2, 2), ("data", "model"))   # repro.launch.mesh
    with mesh_context(mesh), mesh:
        state = init_train_state(...)        # params land FSDP-sharded
        out = train_step(state, batch)       # constrain() sees the mesh

``DeviceRing`` / ``batch_devices`` — round-robin routing of *independent*
dispatches (collated serve batches, data-parallel gradient shards) onto the
replica devices of the active mesh, or every local device when no mesh is
set.  Used by serve/circuit_engine.py and train/circuit_trainer.py::

    ring = DeviceRing()                      # one slot per replica device
    i = ring.next_index()                    # thread-safe round-robin
    batch = ring.put(batch, i)               # device_put onto slot i
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axes (in priority order; multiple axes multiply)
RULES = {
    "batch": ("pod", "data"),
    "sp": ("model",),          # sequence-parallel residual storage
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "embed": ("data",),        # FSDP axis for parameter d_model dims
    "kv_seq": ("model",),      # decode-time KV/state cache sequence sharding
    "ssm_heads": ("model",),
    None: (),
}

_state = threading.local()


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def _axes_for(logical: Optional[str], mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in RULES.get(logical, ()) if a in mesh.axis_names)


def make_pspec(shape: Sequence[int], axes: Sequence[Optional[str]],
               mesh: Mesh) -> P:
    """PartitionSpec for ``shape`` under logical ``axes`` — replicating any
    dim whose size is not divisible by its mesh-axis product."""
    assert len(shape) == len(axes), (shape, axes)
    spec = []
    for dim, name in zip(shape, axes):
        mesh_axes = _axes_for(name, mesh)
        size = 1
        for a in mesh_axes:
            size *= mesh.shape[a]
        if mesh_axes and dim % size == 0 and dim > 0:
            spec.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        else:
            spec.append(None)
    return P(*spec)


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh or
    inside a manual (shard_map) region, where shard_map's specs govern."""
    mesh = get_mesh()
    if mesh is None or mesh.size == 1 or manual_axes():
        return x
    spec = make_pspec(x.shape, axes, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_sharding(shape: Sequence[int], axes: Sequence[Optional[str]],
                   mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, make_pspec(shape, axes, mesh))


def batch_axes(mesh: Optional[Mesh] = None) -> Tuple[str, ...]:
    mesh = mesh or get_mesh()
    return tuple(a for a in ("pod", "data") if mesh and a in mesh.axis_names)


def batch_devices(mesh: Optional[Mesh] = None) -> Tuple:
    """Devices that can each run an *independent* batch: one per batch-axis
    ("pod" × "data") coordinate of the active mesh — model-axis peers hold
    shards of ONE replica, so only the first device of each model group is a
    routing target — or every local device when no mesh is set."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return tuple(jax.local_devices())
    dv = np.asarray(mesh.devices)
    ax = batch_axes(mesh)
    if not ax:
        return (dv.flat[0],)
    names = list(mesh.axis_names)
    perm = [names.index(a) for a in ax] + \
           [i for i, n in enumerate(names) if n not in ax]
    n = 1
    for a in ax:
        n *= mesh.shape[a]
    return tuple(np.transpose(dv, perm).reshape(n, -1)[:, 0])


class DeviceRing:
    """Round-robin router for embarrassingly parallel dispatches, with
    per-slot health state.

    Independent collated batches (serving) and gradient shards (data-
    parallel training) have no cross-device dataflow, so routing them onto
    distinct devices is pure throughput.  ``devices=None`` resolves via
    :func:`batch_devices` at construction time; ``next_index`` is a
    thread-safe round-robin counter (callers on packing-pool threads share
    one ring).

    Health (opt-in — nothing changes until a caller reports failures):
    ``record_failure(i)`` / ``record_success(i)`` track consecutive
    failures per slot.  ``quarantine_after`` consecutive failures move a
    slot to ``"quarantined"`` and ``next_index`` routes around it; after
    ``probe_interval_s`` the slot is handed out ONCE as a probe
    (``"probing"``) — a success re-admits it, a failure re-quarantines and
    restarts the probe clock.  With every slot down the ring degrades to
    plain round-robin over all slots (refusing service is strictly worse
    than trying a sick device).  The serve engine is the caller
    (serve/circuit_engine.py containment ladder); DESIGN.md §10."""

    UP, QUARANTINED, PROBING = "up", "quarantined", "probing"

    def __init__(self, devices: Optional[Sequence] = None, *,
                 quarantine_after: int = 3,
                 probe_interval_s: float = 1.0,
                 clock=time.monotonic):
        self.devices = tuple(devices) if devices is not None \
            else batch_devices()
        assert self.devices, "DeviceRing needs at least one device"
        self.quarantine_after = quarantine_after
        self.probe_interval_s = probe_interval_s
        self._clock = clock
        self._count = itertools.count()
        n = len(self.devices)
        self._hlock = threading.Lock()
        self._state = [self.UP] * n
        self._fails = [0] * n               # consecutive failures per slot
        self._since = [0.0] * n             # quarantine timestamp per slot
        self.quarantines = 0
        self.probes = 0
        self.readmissions = 0

    def __len__(self) -> int:
        return len(self.devices)

    def next_index(self) -> int:
        with self._hlock:
            now = self._clock()
            for i, st in enumerate(self._state):
                if st == self.QUARANTINED and \
                        now - self._since[i] >= self.probe_interval_s:
                    # one probe dispatch; PROBING keeps the slot out of the
                    # healthy rotation until the probe resolves
                    self._state[i] = self.PROBING
                    self.probes += 1
                    return i
            healthy = [i for i, st in enumerate(self._state)
                       if st == self.UP]
            if not healthy:                 # no survivors: degrade, serve
                return next(self._count) % len(self.devices)
            return healthy[next(self._count) % len(healthy)]

    def record_failure(self, index: int) -> None:
        """A device-attributable failure on slot ``index`` (dispatch /
        transfer / watchdog timeout — NOT data faults)."""
        with self._hlock:
            i = index % len(self.devices)
            self._fails[i] += 1
            if self._state[i] == self.PROBING:
                self._state[i] = self.QUARANTINED     # probe failed
                self._since[i] = self._clock()
            elif self._state[i] == self.UP and \
                    self._fails[i] >= self.quarantine_after:
                self._state[i] = self.QUARANTINED
                self._since[i] = self._clock()
                self.quarantines += 1

    def release(self, index: int) -> None:
        """The caller obtained ``index`` but never exercised the device
        (e.g. host-side collation failed first).  A probe handout must not
        stay in ``"probing"`` limbo — put it back to ``"quarantined"``
        WITHOUT resetting the probe clock, so the very next ``next_index``
        re-probes; no failure is attributed (the device was untouched)."""
        with self._hlock:
            i = index % len(self.devices)
            if self._state[i] == self.PROBING:
                self._state[i] = self.QUARANTINED

    def record_success(self, index: int) -> None:
        with self._hlock:
            i = index % len(self.devices)
            self._fails[i] = 0
            if self._state[i] != self.UP:
                self._state[i] = self.UP              # probe succeeded
                self.readmissions += 1

    def quarantine(self, index: int) -> None:
        """Force a slot down (ops/bench hook: degraded-mode measurement,
        draining a device for maintenance)."""
        with self._hlock:
            i = index % len(self.devices)
            if self._state[i] == self.UP:
                self.quarantines += 1
            self._state[i] = self.QUARANTINED
            self._since[i] = self._clock()

    @property
    def quarantined(self) -> Tuple[int, ...]:
        with self._hlock:
            return tuple(i for i, st in enumerate(self._state)
                         if st != self.UP)

    def health(self) -> dict:
        """Snapshot for ``stats()``: per-slot state plus lifetime
        quarantine/probe/readmission counters."""
        with self._hlock:
            return dict(states=list(self._state),
                        consecutive_failures=list(self._fails),
                        quarantines=self.quarantines,
                        probes=self.probes,
                        readmissions=self.readmissions)

    def put(self, tree, index: int):
        """``jax.device_put`` a pytree onto ring slot ``index``."""
        return jax.device_put(tree, self.devices[index % len(self.devices)])


_SHARD_MESHES: dict = {}


def shard_mesh(n_shards: int) -> Mesh:
    """1-D ``("shard",)`` mesh over the first ``n_shards`` local devices,
    memoized.  The giant-graph executor (kernels/ops.py::
    drspmm_multi_sharded) keys its jit cache on plan identity; an
    identity-stable mesh keeps those cache entries from splitting."""
    m = _SHARD_MESHES.get(n_shards)
    if m is None:
        devs = jax.local_devices()
        if n_shards > len(devs):
            hint = (f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{n_shards} before the first jax import for virtual "
                    f"CPU devices" if devs[0].platform == "cpu" else
                    f"this host has {len(devs)} {devs[0].platform} "
                    f"device(s) ({devs[0].device_kind})")
            raise ValueError(f"shard_mesh({n_shards}) needs {n_shards} "
                             f"devices, {len(devs)} visible — {hint}")
        m = Mesh(np.asarray(devs[:n_shards]), ("shard",))
        _SHARD_MESHES[n_shards] = m
    return m


def manual_axes() -> Tuple[str, ...]:
    """Mesh axes already in Manual mode (i.e. we are inside a shard_map).
    Nested full-manual shard_maps over a mismatched mesh are rejected by
    JAX, so callers fall back to plain jnp in that case."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return ()
    return tuple(n for n, t in zip(m.axis_names, m.axis_types)
                 if t == jax.sharding.AxisType.Manual)
