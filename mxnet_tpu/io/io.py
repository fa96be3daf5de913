"""Data iterators.

Reference: ``python/mxnet/io/io.py`` (DataDesc:41, DataBatch:114,
DataIter:178, ResizeIter:280, PrefetchingIter:345, NDArrayIter) and the C++
iterators in ``src/io/`` (iter_mnist.cc, iter_csv.cc, iter_libsvm.cc).

TPU note: the pipeline's job is to keep the chip fed — iterators produce
host numpy batches and a background-thread prefetcher overlaps host decode
with device compute (the reference uses dmlc::ThreadedIter the same way,
iter_prefetcher.h).  Conversion to device arrays happens at consumption so
XLA's async transfer overlaps too.
"""

from __future__ import annotations

import collections
import gzip
import os
import queue
import struct
import threading

import numpy as _np

from ..base import np_dtype
from .. import ndarray as nd
from .. import profiler as _prof
from .. import sanitizer as _san
from ..ndarray import NDArray
from ..observability import metrics as _obs_metrics

# module-level ref — sampled once per consumed batch
_PREFETCH_DEPTH = _obs_metrics.gauge(
    "prefetch_queue_depth",
    "batches buffered in the PrefetchingIter producer queue")

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
           "PrefetchingIter", "MNISTIter", "CSVIter", "LibSVMIter"]


class DataDesc(collections.namedtuple("DataDesc", ["name", "shape"])):
    """Name + shape (+dtype/layout) of a data slot
    (reference: io.py DataDesc:41)."""

    def __new__(cls, name, shape, dtype=_np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")


class DataBatch:
    """One mini-batch (reference: io.py DataBatch:114)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None and not isinstance(data, (list, tuple)):
            data = [data]
        if label is not None and not isinstance(label, (list, tuple)):
            label = [label]
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        data_shapes = [d.shape for d in self.data] if self.data else None
        label_shapes = [l.shape for l in self.label] if self.label else None
        return "{}: data shapes: {} label shapes: {}".format(
            type(self).__name__, data_shapes, label_shapes)


class DataIter:
    """Iterator protocol (reference: io.py DataIter:178).

    Resumable position (resilience subsystem): ``state_dict()``
    captures the iterator's mid-epoch cursor — including any
    shuffle order already drawn — and ``load_state()`` restores it,
    so a preempted job's ``TrainJobState`` resumes the data pipeline
    at the exact next batch instead of silently replaying or
    skipping.  The base implementation handles stateless iterators;
    every stateful subclass in this module overrides both."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def state_dict(self):
        """Serializable (JSON-safe) resume position."""
        return {"type": type(self).__name__}

    def _check_state_type(self, state):
        got = state.get("type")
        if got is not None and got != type(self).__name__:
            raise ValueError(
                "data-iterator state was captured from %r but is being "
                "restored into %r — the resumed job must rebuild the "
                "same pipeline" % (got, type(self).__name__))

    def load_state(self, state):
        self._check_state_type(state)

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError


def _init_data(data, allow_empty, default_name):
    """Normalize input data to list of (name, numpy array)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (_np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = collections.OrderedDict([(default_name, data[0])])
        else:
            data = collections.OrderedDict(
                [("_%d_%s" % (i, default_name), d)
                 for i, d in enumerate(data)])
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of "
                        "them or dict with them as values")
    out = collections.OrderedDict()
    for k, v in data.items():
        if isinstance(v, NDArray):
            v = v.asnumpy()
        out[k] = _np.asarray(v)
    return list(out.items())


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (reference: io.py NDArrayIter).

    Elastic partitioning (docs/resilience.md "Elastic training"):
    with ``num_parts > 1`` the iterator walks GLOBAL rounds of
    ``batch_size * num_parts`` samples and yields only this worker's
    ``part_index``-th slice of each round.  All workers share the
    permutation (pass the same ``shuffle_seed``), so the union of all
    parts covers each epoch index exactly once.  ``repartition()``
    changes the layout at a batch boundary — the global cursor is
    preserved, so a dist_sync job that shrinks or grows mid-epoch
    keeps exactly-once coverage, and a mid-epoch joiner restores a
    survivor's ``state_dict()`` and repartitions to its own slot."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label", part_index=0, num_parts=1,
                 shuffle_seed=None):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.idx = _np.arange(self.data[0][1].shape[0])
        self.shuffle = shuffle
        # permutations come from a PRIVATE seeded stream (seed drawn
        # once from global np.random, so np.random.seed reproducibility
        # is preserved): a mid-epoch resume restores (seed, drawn) and
        # every LATER epoch's reset() re-draws in lockstep with the
        # uninterrupted run — global-np.random shuffles could restore
        # the current order but not realign the stream position.  An
        # explicit shuffle_seed makes the order REPRODUCIBLE ACROSS
        # WORKERS — the elastic-partition contract.
        if shuffle:
            self._shuffle_seed = (int(shuffle_seed)
                                  if shuffle_seed is not None
                                  else int(_np.random.randint(
                                      0, 2 ** 31 - 1)))
        else:
            self._shuffle_seed = None
        self._shuffle_drawn = 0
        self.last_batch_handle = last_batch_handle
        self.num_data = self.idx.shape[0]
        self.part_index = int(part_index)
        self.num_parts = max(1, int(num_parts))
        self._check_partition(self.part_index, self.num_parts)
        self.cursor = -self._round
        self.num_source = len(self.data)
        self._cache_data = None
        self.reset()

    @property
    def _round(self):
        """Samples one GLOBAL step consumes across all partitions."""
        return self.batch_size * self.num_parts

    def _check_partition(self, part_index, num_parts):
        if not 0 <= part_index < num_parts:
            raise ValueError("part_index %d not in [0, %d)"
                             % (part_index, num_parts))
        if num_parts > 1 and self.last_batch_handle not in ("pad",
                                                            "discard"):
            raise ValueError(
                "partitioned iteration supports last_batch_handle "
                "'pad' or 'discard', not %r" % self.last_batch_handle)
        if self.num_data < self.batch_size * num_parts:
            raise ValueError(
                "global batch (batch_size %d * num_parts %d) must not "
                "exceed the data size %d"
                % (self.batch_size, num_parts, self.num_data))

    def repartition(self, part_index, num_parts):
        """Re-shard at a batch boundary: this worker becomes slice
        *part_index* of *num_parts*.  The GLOBAL consumed cursor is
        preserved, so across a shrink/grow every remaining sample of
        the epoch is still consumed exactly once (all workers must
        repartition at the same global cursor — the membership
        snapshot of a completed sync round gives them that boundary)."""
        part_index, num_parts = int(part_index), int(num_parts)
        consumed = self.cursor + self._round
        self._check_partition(part_index, num_parts)
        self.part_index, self.num_parts = part_index, num_parts
        self.cursor = consumed - self._round
        self._cache_data = None

    set_partition = repartition

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.label]

    def _reshuffle(self):
        rs = _np.random.RandomState([self._shuffle_seed,
                                     self._shuffle_drawn])
        self._shuffle_drawn += 1
        rs.shuffle(self.idx)

    def hard_reset(self):
        if self.shuffle:
            self._reshuffle()
        self.cursor = -self._round

    def reset(self):
        if self.shuffle:
            self._reshuffle()
        if self.last_batch_handle == "roll_over" and \
                self.num_data - self.batch_size < self.cursor < \
                self.num_data:
            self.cursor = -self.batch_size + \
                (self.cursor - self.num_data)
        else:
            self.cursor = -self._round

    def iter_next(self):
        self.cursor += self._round
        return self.cursor < self.num_data

    def next(self):
        if not self.iter_next():
            raise StopIteration
        if self.last_batch_handle == "discard" and \
                self.cursor + self._round > self.num_data:
            raise StopIteration
        return DataBatch(data=self.getdata(), label=self.getlabel(),
                         pad=self.getpad(), index=self.getindex())

    def _sel(self):
        """The dataset indices of THIS worker's slice of the current
        global round: positions ``[part*b, (part+1)*b)`` of the round
        window starting at ``cursor``; a window past the end wraps to
        the epoch's start (the reference's pad-by-wrapping, extended
        to the partitioned layout — ``getpad()`` names how many of
        this worker's rows are wrap-padding)."""
        lo = self.cursor + self.part_index * self.batch_size
        hi = lo + self.batch_size
        if hi <= self.num_data:
            return self.idx[lo:hi]
        if lo >= self.num_data:
            wrap = _np.arange(lo - self.num_data, hi - self.num_data)
            return self.idx[wrap % self.num_data]
        return _np.concatenate(
            [self.idx[lo:],
             self.idx[_np.arange(hi - self.num_data) % self.num_data]])

    def _getdata(self, data_source):
        sel = self._sel()
        return [nd.array(v[sel], dtype=str(v[sel].dtype)
                         if v.dtype != _np.float64 else "float32")
                for _, v in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label) if self.label else []

    def getindex(self):
        """The GLOBAL dataset indices of this worker's current slice
        (elastic drills assert exactly-once epoch coverage from these;
        wrap-padded rows repeat indices — trim with getpad())."""
        if self.num_parts == 1:
            return None     # legacy contract: plain batches carry None
        return self._sel()

    def getpad(self):
        """How many TRAILING rows of this worker's slice are wrap
        padding (only the final global round of a 'pad' epoch)."""
        if self.last_batch_handle != "pad":
            return 0
        lo = self.cursor + self.part_index * self.batch_size
        hi = lo + self.batch_size
        if hi <= self.num_data:
            return 0
        return min(hi - self.num_data, self.batch_size)

    def state_dict(self):
        """Cursor + the epoch's shuffle order + the private shuffle
        stream position: restoring all three makes a mid-epoch resume
        replay the EXACT remaining batches AND keeps every later
        epoch's re-shuffle in lockstep with the uninterrupted run."""
        return {"type": type(self).__name__,
                "cursor": int(self.cursor),
                "idx": self.idx.tolist() if self.shuffle else None,
                "shuffle_seed": self._shuffle_seed,
                "shuffle_drawn": self._shuffle_drawn,
                "part_index": self.part_index,
                "num_parts": self.num_parts}

    def load_state(self, state):
        """Restore a captured position.  A mid-epoch JOINER restores a
        survivor's state (same permutation + global cursor + the
        survivor's partition layout), then calls ``repartition()``
        with its own slot — the post-resize stream is bit-reproducible
        from jobstate alone."""
        self._check_state_type(state)
        if state.get("idx") is not None:
            idx = _np.asarray(state["idx"], dtype=self.idx.dtype)
            if idx.shape != self.idx.shape:
                raise ValueError(
                    "restored shuffle order has %d indices, dataset "
                    "has %d" % (idx.shape[0], self.idx.shape[0]))
            self.idx = idx
        if state.get("shuffle_seed") is not None:
            self._shuffle_seed = int(state["shuffle_seed"])
            self._shuffle_drawn = int(state.get("shuffle_drawn", 0))
        if state.get("num_parts") is not None:
            part = int(state.get("part_index", 0))
            parts = int(state["num_parts"])
            self._check_partition(part, parts)
            self.part_index, self.num_parts = part, parts
        self.cursor = int(state["cursor"])
        self._cache_data = None


class ResizeIter(DataIter):
    """Resize an iterator to a fixed number of batches
    (reference: io.py ResizeIter:280)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad

    def state_dict(self):
        return {"type": type(self).__name__, "cur": int(self.cur),
                "inner": self.data_iter.state_dict()}

    def load_state(self, state):
        self._check_state_type(state)
        self.cur = int(state["cur"])
        self.current_batch = None
        self.data_iter.load_state(state["inner"])


class PrefetchingIter(DataIter):
    """Background-thread prefetch (reference: io.py PrefetchingIter:345,
    C++ iter_prefetcher.h).

    Failure semantics (resilience subsystem): an exception in the
    producer thread travels to the consumer and is raised from
    ``next()`` ONCE; further ``next()`` calls see ``StopIteration``
    (never a hang on an empty queue whose producer is gone), and
    ``reset()`` fully restores the iterator.  The producer only ever
    blocks on the queue in a stop-aware loop, so ``reset()`` can always
    drain + join it — no deadlock regardless of where the producer was.
    An optional *retry* spec (kwargs for
    :func:`mxnet_tpu.resilience.retry.retry_call`) retries transient
    inner-iterator failures with jittered backoff before surfacing
    them."""

    def __init__(self, iters, rename_data=None, rename_label=None,
                 prefetch_depth=2, retry=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter == 1, "PrefetchingIter wraps one iterator"
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = iters[0].batch_size
        self._depth = prefetch_depth
        self._retry = dict(retry) if retry else None
        self._queue = None
        self._stop = None
        self._thread = None
        self._peek = None
        self.current_batch = None
        # resume bookkeeping: the inner iterator's state at epoch
        # start + how many batches the CONSUMER has taken.  The
        # producer thread runs AHEAD of the consumer, so the inner
        # iterator's live cursor is useless for resume — the pair
        # (epoch-start state, consumed count) is the exact position.
        self._consumed = 0
        self._epoch_state = self._inner_state()
        self._start()

    @property
    def provide_data(self):
        return self.iters[0].provide_data

    @property
    def provide_label(self):
        return self.iters[0].provide_label

    @staticmethod
    def _put(q, stop, item):
        """Stop-aware put: never blocks past a reset() request."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _next_inner(self):
        if self._retry:
            from ..resilience.retry import retry_call
            cfg = dict(self._retry)
            cfg.setdefault("retry_on", (Exception,))
            give_up = tuple(cfg.pop("give_up_on", ()))
            return retry_call(self.iters[0].next,
                              give_up_on=give_up + (StopIteration,),
                              **cfg)
        return self.iters[0].next()

    def _transform(self, batch):
        """Producer-side per-batch hook (runs on the prefetch thread,
        BEFORE the batch enters the ring).  The base class passes
        batches through; :class:`DevicePrefetcher` overrides it to run
        ``jax.device_put`` here so host decode AND the host→device
        transfer overlap device compute."""
        return batch

    def _producer(self, q, stop):
        # q/stop are bound per-thread: a producer abandoned by reset()
        # keeps talking to ITS queue and stop event, never the
        # replacement epoch's
        while not stop.is_set():
            try:
                with _prof.scope("mx.prefetch.source_next", "input"):
                    batch = self._next_inner()
                batch = self._transform(batch)
            except StopIteration:
                self._put(q, stop, None)
                return
            except Exception as e:  # exception travels to consumer
                self._put(q, stop, e)
                # trailing sentinel: after the consumer raises the
                # exception, further next() calls end the epoch
                # instead of hanging on a dead producer
                self._put(q, stop, None)
                return
            if not self._put(q, stop, batch):
                return

    def _start(self):
        self._closed = False
        self._queue = _san.queue(maxsize=self._depth)
        self._stop = _san.event()
        self._thread = _san.thread(
            target=self._producer, args=(self._queue, self._stop),
            daemon=True)
        self._thread.start()

    def _inner_state(self):
        sd = getattr(self.iters[0], "state_dict", None)
        return sd() if sd is not None else None

    def _stop_producer(self):
        import logging
        import time as _time
        self._stop.set()
        # drain-then-join until the producer exits: it can only block
        # in the stop-aware _put, so freeing queue slots always
        # unwedges it (a producer mid-put refills what we drain, hence
        # the loop rather than a single drain).  Bounded: a producer
        # wedged inside the INNER iterator's next() is abandoned — the
        # fresh queue started next detaches it either way
        deadline = _time.monotonic() + 10.0
        while self._thread is not None and self._thread.is_alive():
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.1)
            if _time.monotonic() > deadline:
                logging.getLogger(__name__).warning(
                    "PrefetchingIter: producer thread did not exit "
                    "within 10s (inner iterator wedged?); detaching it")
                break

    def reset(self):
        self._stop_producer()
        self.iters[0].reset()
        self._peek = None
        self.current_batch = None
        self._consumed = 0
        self._epoch_state = self._inner_state()
        self._start()

    def close(self):
        """Stop the producer thread and drop buffered batches (a ring
        of device-resident buffers holds depth×batch bytes of device
        memory until released).  The iterator stays resumable:
        ``reset()`` or ``load_state()`` starts a fresh producer."""
        self._stop_producer()
        self._closed = True
        self._peek = None
        self.current_batch = None

    def state_dict(self):
        """Pass-through position: the inner iterator's state at epoch
        start plus the number of batches actually DELIVERED to the
        consumer (prefetched-but-unconsumed batches belong to the
        resumed run, not this one)."""
        return {"type": type(self).__name__,
                "epoch_start": self._epoch_state,
                "consumed": self._consumed}

    def load_state(self, state):
        self._check_state_type(state)
        if state.get("epoch_start") is None:
            raise ValueError(
                "PrefetchingIter state is not resumable: the wrapped "
                "iterator (%s) has no state_dict()"
                % type(self.iters[0]).__name__)
        self._stop_producer()
        inner = self.iters[0]
        inner.load_state(state["epoch_start"])
        # fast-forward through the already-consumed batches on the
        # CALLER's thread (deterministic inner iterators re-decode the
        # skipped range; no producer races with the skipping)
        consumed = int(state["consumed"])
        for _ in range(consumed):
            inner.next()
        self._peek = None
        self.current_batch = None
        self._consumed = consumed
        self._epoch_state = state["epoch_start"]
        self._start()

    def repartition(self, part_index, num_parts):
        """Elastic re-shard THROUGH the prefetch ring: the producer
        runs ahead of the consumer, so simply delegating would either
        skip the prefetched-but-undelivered batches or replay ones
        already handed out.  Instead the inner iterator is rewound to
        the exact delivered position (epoch-start state + consumed
        fast-forward, the same protocol as :meth:`load_state`),
        repartitioned there, and a fresh producer started — no sample
        is lost or duplicated across the resize."""
        inner = self.iters[0]
        rp = getattr(inner, "repartition", None)
        if rp is None:
            raise AttributeError(
                "wrapped iterator %s has no repartition()"
                % type(inner).__name__)
        if self._epoch_state is None:
            raise ValueError(
                "cannot repartition through %s: the wrapped iterator "
                "(%s) has no state_dict()" % (
                    type(self).__name__, type(inner).__name__))
        self._stop_producer()
        inner.load_state(self._epoch_state)
        for _ in range(self._consumed):
            inner.next()
        rp(part_index, num_parts)
        self._peek = None
        self.current_batch = None
        self._consumed = 0
        self._epoch_state = self._inner_state()
        self._start()

    def _note_occupancy(self, occupancy):
        """Consumer-side hook, called with the ring occupancy right
        before popping (0 = the consumer is about to block on input).
        Subclasses override to feed their own instruments."""
        _PREFETCH_DEPTH.set(occupancy)

    def _note_delivery(self, occupancy, wait_s):
        """Consumer-side hook, called after a REAL batch (not the
        end-of-epoch sentinel or a producer exception) was popped:
        *wait_s* is how long the consumer blocked on the ring."""

    def next(self):
        if self._peek is not None:
            batch, self._peek = self._peek, None
            self.current_batch = batch
            return batch
        if self._closed:
            # the drained queue has no producer — blocking on it would
            # hang forever, so fail loudly instead
            raise RuntimeError(
                "%s.next() after close(): the producer is stopped and "
                "the ring drained; reset() or load_state() starts a "
                "fresh producer" % type(self).__name__)
        occupancy = self._queue.qsize()
        self._note_occupancy(occupancy)
        # the span's two clock reads are the wait the instruments see
        with _prof.scope("mx.prefetch.wait", "input") as wait:
            item = self._queue.get()
        if item is None:
            raise StopIteration
        if isinstance(item, Exception):
            raise item
        self._note_delivery(occupancy, wait.end - wait.start)
        self._consumed += 1
        self.current_batch = item
        return item

    def iter_next(self):
        """Peek semantics: a True return makes the batch available via
        getdata/getlabel AND the next next() call (no batch is dropped)."""
        if self._peek is not None:
            return True
        try:
            batch = self.next()  # sets current_batch
        except StopIteration:
            return False
        self._peek = batch  # next() will return this same batch
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class MNISTIter(DataIter):
    """idx-ubyte MNIST reader (reference: src/io/iter_mnist.cc:260)."""

    def __init__(self, image, label, batch_size=128, shuffle=True,
                 flat=False, silent=False, seed=0, input_shape=None,
                 **kwargs):
        data, labels = _read_idx_images(image), _read_idx_labels(label)
        if flat:
            data = data.reshape(data.shape[0], -1)
        else:
            data = data.reshape(data.shape[0], 1, data.shape[1],
                                data.shape[2])
        if input_shape is not None:
            data = data.reshape((data.shape[0],) + tuple(input_shape))
        data = data.astype(_np.float32) / 255.0
        self._inner = NDArrayIter(data, labels.astype(_np.float32),
                                  batch_size=batch_size, shuffle=shuffle,
                                  last_batch_handle="discard")
        super().__init__(batch_size)

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()

    def state_dict(self):
        return {"type": type(self).__name__,
                "inner": self._inner.state_dict()}

    def load_state(self, state):
        self._check_state_type(state)
        self._inner.load_state(state["inner"])


def _open_maybe_gz(path):
    if path.endswith(".gz"):
        return gzip.open(path, "rb")
    return open(path, "rb")


def _read_idx_images(path):
    with _open_maybe_gz(path) as f:
        magic, n, rows, cols = struct.unpack(">IIII", f.read(16))
        assert magic == 2051, "bad idx image magic in %s" % path
        buf = f.read(n * rows * cols)
        return _np.frombuffer(buf, dtype=_np.uint8).reshape(n, rows, cols)


def _read_idx_labels(path):
    with _open_maybe_gz(path) as f:
        magic, n = struct.unpack(">II", f.read(8))
        assert magic == 2049, "bad idx label magic in %s" % path
        return _np.frombuffer(f.read(n), dtype=_np.uint8)


class CSVIter(DataIter):
    """Dense CSV reader (reference: src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None,
                 label_shape=(1,), batch_size=1, round_batch=True,
                 **kwargs):
        data = _np.loadtxt(data_csv, delimiter=",",
                           dtype=_np.float32, ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = _np.loadtxt(label_csv, delimiter=",",
                                dtype=_np.float32, ndmin=1)
        else:
            label = _np.zeros((data.shape[0],), _np.float32)
        self._inner = NDArrayIter(
            data, label, batch_size=batch_size,
            last_batch_handle="pad" if round_batch else "discard")
        super().__init__(batch_size)

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()

    def state_dict(self):
        return {"type": type(self).__name__,
                "inner": self._inner.state_dict()}

    def load_state(self, state):
        self._check_state_type(state)
        self._inner.load_state(state["inner"])


class LibSVMIter(DataIter):
    """Sparse LibSVM reader producing CSR batches
    (reference: src/io/iter_libsvm.cc — feeds example/sparse)."""

    def __init__(self, data_libsvm, data_shape, label_libsvm=None,
                 batch_size=1, round_batch=True, **kwargs):
        num_features = data_shape[0] if isinstance(data_shape,
                                                   (tuple, list)) \
            else data_shape
        labels = []
        indptr = [0]
        indices = []
        values = []
        with open(data_libsvm) as f:
            for line in f:
                parts = line.strip().split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                for tok in parts[1:]:
                    i, v = tok.split(":")
                    indices.append(int(i))
                    values.append(float(v))
                indptr.append(len(indices))
        self._values = _np.asarray(values, _np.float32)
        self._indices = _np.asarray(indices, _np.int32)
        self._indptr = _np.asarray(indptr, _np.int32)
        self._labels = _np.asarray(labels, _np.float32)
        self._num_features = num_features
        self.batch_size = batch_size
        self._num = len(labels)
        self._cursor = 0
        self._round = round_batch
        super().__init__(batch_size)

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size, self._num_features))]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (self.batch_size,))]

    def reset(self):
        self._cursor = 0

    def next(self):
        from ..ndarray import sparse as _sp
        if self._cursor >= self._num:
            raise StopIteration
        lo = self._cursor
        hi = lo + self.batch_size
        pad = 0
        if hi > self._num:
            if not self._round:
                raise StopIteration
            pad = hi - self._num  # wrap the final batch (reference
            # round_batch semantics, iter_libsvm.cc)
        self._cursor = hi
        rows = [(r % self._num) for r in range(lo, hi)]
        values, indices, indptr = [], [], [0]
        for r in rows:
            s, e = self._indptr[r], self._indptr[r + 1]
            values.append(self._values[s:e])
            indices.append(self._indices[s:e])
            indptr.append(indptr[-1] + (e - s))
        batch = _sp.csr_matrix(
            (_np.concatenate(values) if values else
             _np.zeros(0, _np.float32),
             _np.concatenate(indices) if indices else
             _np.zeros(0, _np.int32),
             _np.asarray(indptr, _np.int32)),
            shape=(self.batch_size, self._num_features))
        label = nd.array(self._labels[[r for r in rows]])
        return DataBatch(data=[batch], label=[label], pad=pad)

    def iter_next(self):
        if self._round:
            return self._cursor < self._num
        return self._cursor + self.batch_size <= self._num

    def state_dict(self):
        return {"type": type(self).__name__,
                "cursor": int(self._cursor)}

    def load_state(self, state):
        self._check_state_type(state)
        self._cursor = int(state["cursor"])
