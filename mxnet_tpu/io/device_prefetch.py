"""Device-resident input pipeline — host↔device overlap.

``PrefetchingIter`` (io.py) overlaps host decode with host compute
only: every batch it hands out is still a HOST array, and the training
loop pays a synchronous ``jax.device_put`` inside the step loop (the
reference framework's ``iter_prefetcher.h`` has the same shape — its
prefetch thread stops at host memory).  :class:`DevicePrefetcher` goes
one layer lower: the background producer runs host decode **and** the
host→device transfer, parking finished batches in a depth-K ring of
device-resident buffers, so by the time the consumer asks for batch N
its bytes are already on the chip and the fused train step dispatches
with zero input-side host work (``device_put_elided_total`` counts the
transfers the step loop consequently skips — see
docs/perf_input_pipeline.md).

Placement modes:

* plain device (default / ``device=``): ``jax.device_put`` onto one
  device — the Module path; the executor's ``_place`` then elides its
  own put because the batch is already committed there;
* ``mesh=``/``spec=``: ``jax.device_put`` with a
  ``NamedSharding(mesh, spec)`` (default ``P('dp')``) — the
  ParallelTrainer path; ``_device_batch`` sees the matching sharding
  and skips its transfer, so sharded batches are free.

Everything threaded is built from the :mod:`..sanitizer` factories, so
``MXNET_SAN=all`` / ``pytest --graftsan`` audits the ring's locks and
producer thread like every other subsystem.  ``state_dict`` /
``load_state`` pass through :class:`PrefetchingIter`'s (epoch-start
inner state, batches consumed) accounting, so a mid-epoch checkpoint
taken through the wrapper resumes bit-exactly (the producer runs AHEAD
of the consumer; prefetched-but-unconsumed device batches belong to
the resumed run).
"""

from __future__ import annotations

from .io import DataBatch, PrefetchingIter
from ..ndarray import NDArray
from ..ndarray.ndarray import _already_placed, _DEVICE_PUT_ELIDED
from ..observability import metrics as _obs_metrics
from .. import profiler as _prof

__all__ = ["DevicePrefetcher", "maybe_wrap"]

# module-level instrument refs — observed once per consumed batch (the
# ndarray.py hot-path discipline: no registry lookup per step)
_INPUT_WAIT = _obs_metrics.histogram(
    "input_wait_seconds",
    "host time the training loop waited on the device-prefetch ring "
    "for its next batch (steady-state overlap keeps this near zero)")
_STEPS_STALLED = _obs_metrics.counter(
    "steps_input_stalled_total",
    "training steps that found the device-prefetch ring empty and had "
    "to wait on input (the input pipeline is the bottleneck)")
_RING_OCCUPANCY = _obs_metrics.gauge(
    "device_prefetch_ring_occupancy",
    "device-resident batches parked in the DevicePrefetcher ring when "
    "the consumer asked for one (0 = consumer outrunning the producer)")


class DevicePrefetcher(PrefetchingIter):
    """Wrap a ``DataIter``/``DataLoader``-style iterator so batches
    arrive **device-resident**.

    Parameters
    ----------
    iters : DataIter
        The host-side iterator to wrap (anything with the DataIter
        protocol; gluon DataLoaders can be adapted via NDArrayIter).
    depth : int
        Ring depth K: how many decoded-and-transferred batches may be
        in flight ahead of the consumer.  Device memory cost is
        depth × batch bytes; 2 hides decode behind compute, deeper
        rings ride out decode-time jitter.
    device : Context, str, or jax.Device, optional
        Placement target for plain (non-mesh) mode; defaults to the
        current context's device.
    mesh : jax.sharding.Mesh, optional
        When given, batches are placed with
        ``NamedSharding(mesh, spec)`` instead of a single device —
        hand a ``ParallelTrainer`` its ``trainer.mesh`` and
        ``fit_batch`` consumes the batch with zero transfers.
    spec : jax.sharding.PartitionSpec, optional
        Data sharding spec in mesh mode (default ``P('dp')`` — batch
        rows over the data-parallel axis).
    label_spec : PartitionSpec, optional
        Label sharding spec (defaults to *spec*).
    retry : dict, optional
        Passed through to :class:`PrefetchingIter` (transient inner
        iterator failures retried with jittered backoff).

    Sparse batches (CSR/row-sparse containers) pass through
    un-transferred — their carriers move at consumption like before.
    The ring buffers are never donated: the fused step's donation
    covers weights/optimizer state only, so a buffered batch can be
    replayed (chaos NaN-poisoning, monitors) safely.
    """

    def __init__(self, iters, depth=2, device=None, mesh=None, spec=None,
                 label_spec=None, rename_data=None, rename_label=None,
                 retry=None):
        # placement target resolved BEFORE the producer thread starts
        # (super().__init__ launches it)
        self._sharding = None
        self._label_sharding = None
        self._device = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = spec if spec is not None else P("dp")
            self._sharding = NamedSharding(mesh, spec)
            self._label_sharding = NamedSharding(
                mesh, label_spec if label_spec is not None else spec)
        else:
            self._device = self._resolve_device(device)
        super().__init__(iters, rename_data=rename_data,
                         rename_label=rename_label,
                         prefetch_depth=depth, retry=retry)

    @staticmethod
    def _resolve_device(device):
        from ..context import Context, current_context
        if device is None:
            return current_context().jax_device
        if isinstance(device, (Context, str)):
            return Context(device).jax_device
        return device        # a live jax.Device

    # -- producer-side placement ------------------------------------------
    def _put_array(self, arr, target):
        """One array → device-resident NDArray (runs on the producer
        thread).  Sparse containers (CSR/RSP carry aux tables the jit
        consumes at bind time) pass through untouched; an array the
        inner iterator already committed to the target skips the
        re-put (the elision the satellite counter tracks)."""
        import jax
        if isinstance(arr, NDArray):
            if getattr(arr, "_aux", None) is not None:
                return arr   # sparse: moved at consumption, as before
            data = arr._data
        else:
            data = arr       # numpy (or jax) array
        if self._sharding is None:
            if _already_placed(data, target):
                _DEVICE_PUT_ELIDED.inc()
                return arr if isinstance(arr, NDArray) else NDArray(data)
        elif isinstance(data, jax.Array) and \
                getattr(data, "sharding", None) == target:
            _DEVICE_PUT_ELIDED.inc()
            return arr if isinstance(arr, NDArray) else NDArray(data)
        return NDArray(jax.device_put(data, target))

    def _transform(self, batch):
        data_target = self._sharding if self._sharding is not None \
            else self._device
        label_target = self._label_sharding if self._label_sharding is \
            not None else self._device
        with _prof.scope("mx.prefetch.device_put", "input"):
            data = [self._put_array(a, data_target) for a in batch.data] \
                if batch.data else batch.data
            label = [self._put_array(a, label_target)
                     for a in batch.label] \
                if batch.label else batch.label
        out = DataBatch(data=data, label=label, pad=batch.pad,
                        index=batch.index, bucket_key=batch.bucket_key,
                        provide_data=batch.provide_data,
                        provide_label=batch.provide_label)
        return out

    # -- consumer side (the ring-pop protocol itself lives in
    #    PrefetchingIter.next(); only the instruments differ) -------------
    def _note_occupancy(self, occupancy):
        # occupancy sampled per consumed batch; 0 = the step is about
        # to stall on input
        _RING_OCCUPANCY.set(occupancy)

    def _note_delivery(self, occupancy, wait_s):
        _INPUT_WAIT.observe(wait_s)
        if occupancy == 0:
            # a real batch arrived only after the consumer blocked on
            # an empty ring — this step was input-bound
            _STEPS_STALLED.inc()


def maybe_wrap(train_data, device_prefetch, device=None, mesh=None,
               decode_only=False):
    """Resolve the ``fit(device_prefetch=...)`` /
    ``MXNET_DEVICE_PREFETCH`` knob: returns ``(iterator, created)``
    where *created* says a wrapper was built here (the caller owns
    ``close()``-ing it when the loop ends).

    ``device_prefetch`` semantics: ``None`` → consult the env knob;
    ``True`` → default ring depth 2; an int → that ring depth;
    ``0``/``False`` → explicitly off (overrides the env knob).
    An iterator that is already a PrefetchingIter (DevicePrefetcher
    included) is never re-wrapped.

    ``decode_only=True`` wraps with a host-side
    :class:`PrefetchingIter` instead — for placements this layer
    cannot produce (a multi-host global batch belongs to
    ``host_local_to_global``): decode still overlaps compute, and the
    consumer keeps its own placement path without paying a wasted
    single-device transfer first.
    """
    if device_prefetch is None:
        from ..config import get_env
        device_prefetch = get_env("MXNET_DEVICE_PREFETCH")
    if not device_prefetch:
        return train_data, False
    depth = 2 if device_prefetch is True else int(device_prefetch)
    if decode_only:
        # any PrefetchingIter already overlaps decode — re-wrapping
        # would only stack a second producer thread
        if isinstance(train_data, PrefetchingIter):
            return train_data, False
        return PrefetchingIter(train_data, prefetch_depth=depth), True
    if isinstance(train_data, DevicePrefetcher):
        return train_data, False
    return DevicePrefetcher(train_data, depth=depth, device=device,
                            mesh=mesh), True
