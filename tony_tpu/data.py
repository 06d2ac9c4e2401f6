"""Multi-host input pipeline: per-process shards assembled into global
device arrays.

The reference has no data subsystem — feeding was entirely the user
script's problem (SURVEY.md §2.2 examples read MNIST locally per worker).
On TPU the idiomatic shape is: every process loads ONLY its slice of the
global batch, and `jax.make_array_from_process_local_data` assembles the
logical global array laid out by a `NamedSharding` — no host ever
materializes the full batch, and the arrays land already sharded for the
train step (scaling-book input recipe).

Pieces:
- ``global_batch_sharding(mesh)`` — the standard batch layout (leading
  dim over ``dcn_dp × dp × fsdp``; alias of ``parallel.mesh
  .batch_sharding``, the single source of truth).
- ``ShardedBatchIterator`` — wraps any per-sample source callable and
  yields globally-sharded pytrees; deterministic per (seed, step,
  process), so restarts resume identically (checkpoint/resume
  composability).
- ``synthetic_lm_batches`` — the zero-dependency token source used by
  benches/examples (swap for a real tokenized dataset reader).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from tony_tpu import telemetry
from tony_tpu.parallel.mesh import batch_sharding as global_batch_sharding


def process_batch_slice(global_batch: int, rank: Optional[int] = None,
                        world: Optional[int] = None) -> slice:
    """This process's contiguous row range of the global batch.

    ``rank``/``world`` default to the jax distributed runtime; pass them
    explicitly for elastic gangs (coordinator/elastic.py): after a
    resize the executor re-exports the DENSE rank and world
    (TASK_INDEX/TASK_NUM, TONY_GLOBAL_RANK/TONY_GLOBAL_WORLD) and the
    same global batch re-splits across the surviving ranks — every row
    of every step is consumed by exactly one process at whatever world
    size executed that step, so a shrink drops no sample and duplicates
    none."""
    n = int(world) if world is not None else jax.process_count()
    i = int(rank) if rank is not None else jax.process_index()
    if not 0 <= i < n:
        raise ValueError(f"rank {i} outside world of {n}")
    if global_batch % n:
        raise ValueError(
            f"global batch {global_batch} not divisible by process count {n}")
    per = global_batch // n
    return slice(i * per, (i + 1) * per)


@dataclasses.dataclass
class ShardedBatchIterator:
    """Yield globally-sharded batches from a per-process loader.

    ``load_local(step, rows)`` returns this process's rows of the global
    batch for ``step`` as a pytree of numpy/jax arrays with leading dim
    ``rows.stop - rows.start``. The iterator assembles them into global
    ``jax.Array``s laid out by ``shardings`` (a pytree matching the batch,
    or a single sharding applied to every leaf).

    ``prefetch`` (default 2) double-buffers: a daemon thread loads and
    device-puts batch N+1..N+prefetch while step N computes, so the host
    read + H2D transfer hide behind the accelerator (the training loop's
    ``__next__`` returns an already-device-resident batch). 0 = fully
    synchronous (the pre-r5 behavior). ``step`` reports the next step the
    CONSUMER will see — checkpoint/resume keys off consumed batches, not
    what the buffer got ahead to."""

    mesh: Mesh
    global_batch: int
    load_local: Callable[[int, slice], Dict[str, Any]]
    shardings: Optional[Any] = None
    start_step: int = 0
    prefetch: int = 2

    def __post_init__(self):
        self._step = self.start_step        # next step the WORKER loads
        self._consumed = self.start_step    # next step the CONSUMER gets
        self._rows = process_batch_slice(self.global_batch)
        self._q: Optional[queue.Queue] = None
        self._worker: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()

    @property
    def step(self) -> int:
        return self._consumed

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self

    def _assemble(self, step: int) -> Dict[str, Any]:
        local = self.load_local(step, self._rows)

        def to_global(x, sharding):
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(x))

        if self.shardings is None or isinstance(self.shardings,
                                                NamedSharding):
            default = self.shardings
            return jax.tree.map(
                lambda x: to_global(
                    x, default or global_batch_sharding(
                        self.mesh, extra_dims=np.asarray(x).ndim - 1)),
                local)
        return jax.tree.map(to_global, local, self.shardings)

    def _worker_loop(self, stop: threading.Event, q: "queue.Queue",
                     step: int) -> None:
        # This generation's queue/event/step arrive as ARGUMENTS, bound
        # by __next__ at Thread construction: a worker that outlives a
        # close()+restart (join timeout) must keep talking to ITS queue,
        # never the successor's — and must not read or mutate the shared
        # step counter either (a late `self._step += 1` from an
        # abandoned worker made the restarted one silently skip a
        # batch). Snapshotting inside the loop body was not enough: an
        # abandoned worker that had not yet been SCHEDULED when the
        # restart happened would snapshot the successor's state and feed
        # duplicate batches into the new queue.
        while not stop.is_set():
            try:
                item = self._assemble(step)
                step += 1
            except BaseException as e:  # noqa: BLE001 — surface on get()
                item = _PrefetchError(e)
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    break
                except queue.Full:
                    continue
            if isinstance(item, _PrefetchError):
                return                  # consumer re-raises; don't spin

    def __next__(self) -> Dict[str, Any]:
        # Step-time attribution rides for free: the consumer-side wait —
        # the whole assemble when synchronous, the queue wait when the
        # prefetch worker is behind, ~0 when it is ahead — IS the
        # training loop's input stall, telemetry's data_wait phase.
        if self.prefetch <= 0:
            with telemetry.phase("data_wait"):
                batch = self._assemble(self._consumed)
            self._consumed += 1
            return batch
        if self._worker is None:
            # Fresh event per worker: a close() (or the error path below)
            # sets the old one, and a restarted worker must not inherit a
            # stop signal it would obey before producing anything (the
            # consumer's q.get() would deadlock).
            self._stop_evt = threading.Event()
            self._step = self._consumed    # resume where the consumer is
            self._q = queue.Queue(maxsize=self.prefetch)
            self._worker = threading.Thread(
                target=self._worker_loop, name="tony-data-prefetch",
                args=(self._stop_evt, self._q, self._step),
                daemon=True)
            self._worker.start()
        with telemetry.phase("data_wait"):
            item = self._q.get()
        if isinstance(item, _PrefetchError):
            self.close()
            raise item.exc
        self._consumed += 1
        return item

    def close(self) -> None:
        """Stop the prefetch thread (idempotent). Iterators die with their
        (daemon) thread anyway; close() makes teardown deterministic for
        tests and bounded-lifetime loops."""
        self._stop_evt.set()
        if self._worker is not None:
            # Unblock a worker parked on a full queue.
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._worker.join(timeout=5)
            self._worker = None


class _PrefetchError:
    """Exception envelope crossing the prefetch queue."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def synthetic_lm_batches(mesh: Mesh, global_batch: int, seq: int,
                         vocab_size: int, seed: int = 0,
                         start_step: int = 0) -> ShardedBatchIterator:
    """Deterministic synthetic token batches: row ``r`` of step ``s`` is a
    pure function of (seed, s, r), so any process layout — and any restart
    — sees the same global batch."""

    def load_local(step: int, rows: slice) -> Dict[str, Any]:
        out = np.empty((rows.stop - rows.start, seq), np.int32)
        for j, r in enumerate(range(rows.start, rows.stop)):
            rng = np.random.default_rng(
                np.random.SeedSequence([seed, step, r]))
            out[j] = rng.integers(0, vocab_size, size=seq, dtype=np.int32)
        return {"tokens": out}

    return ShardedBatchIterator(mesh=mesh, global_batch=global_batch,
                                load_local=load_local,
                                start_step=start_step)


class TokenFileDataset:
    """Memory-mapped flat token corpus (the nanoGPT/MaxText ``.bin``
    shape: one contiguous array of token ids, uint16 or uint32).

    Each (step, row) of the global batch reads a ``seq``-token window at
    a position that is a pure function of (seed, step, row) — so every
    process computes ONLY its rows (mmap pages the bytes it touches, no
    host ever loads the corpus), any process layout sees the same global
    batch, and a restart at ``start_step`` resumes the identical stream
    (the checkpoint/resume contract of ``ShardedBatchIterator``). Random
    windows are the standard LM pretraining sampling; pair with
    ``write_token_file`` for building corpora in tests/tools."""

    def __init__(self, path: str, seq: int, dtype=np.uint16,
                 seed: int = 0):
        # NB: the seed must be explicit, never derived from hash(path) —
        # Python string hashing is salted per process, which would hand
        # every host a different "global" batch.
        self.path = path
        self.seq = seq
        self.seed = seed
        self.tokens = np.memmap(path, dtype=dtype, mode="r")
        # A window of exactly ``seq`` tokens is one complete sample — the
        # loss shifts inside the batch (causal_lm_loss: tokens[:, 1:]).
        if len(self.tokens) < seq:
            raise ValueError(
                f"{path}: corpus has {len(self.tokens)} tokens, need at "
                f"least seq = {seq}")

    def load_local(self, step: int, rows: slice) -> Dict[str, Any]:
        n = rows.stop - rows.start
        out = np.empty((n, self.seq), np.int32)
        span = len(self.tokens) - self.seq + 1   # every window, incl. last
        for j, r in enumerate(range(rows.start, rows.stop)):
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, step, r]))
            off = int(rng.integers(0, span))
            out[j] = self.tokens[off:off + self.seq].astype(np.int32)
        return {"tokens": out}


def token_file_batches(mesh: Mesh, path: str, global_batch: int, seq: int,
                       dtype=np.uint16, seed: int = 0,
                       start_step: int = 0) -> ShardedBatchIterator:
    """Globally-sharded LM batches from a memory-mapped token file."""
    ds = TokenFileDataset(path, seq, dtype=dtype, seed=seed)
    return ShardedBatchIterator(mesh=mesh, global_batch=global_batch,
                                load_local=ds.load_local,
                                start_step=start_step)


def pack_documents(docs, seq: int, eos_id: int, pad_id: int = 0):
    """Pack variable-length tokenized documents into fixed [N, seq] rows —
    the shape XLA wants (static; no per-batch padding waste).

    GPT-style greedy packing: documents are concatenated, each terminated
    by ``eos_id``, and the stream is sliced into rows of ``seq``. Returns
    ``(tokens, loss_mask)`` int32/float32 arrays where the mask is 0 only
    on the final row's padding — next-token targets crossing a document
    boundary stay in the loss (standard pretraining practice; the EOS
    token is what the model learns as the boundary). Note: attention also
    crosses packed-document boundaries (no segment masking) — acceptable
    for pretraining, not for SFT-style strict isolation.

    Deterministic and order-preserving, so every process packing the same
    corpus sees identical rows (the ShardedBatchIterator contract). Feed
    the result through ``write_token_file``/``TokenFileDataset`` for the
    mmap path, or slice rows directly for small corpora.
    """
    if seq < 2:
        raise ValueError(f"seq must be >= 2, got {seq}")
    eos = np.asarray([eos_id], np.int32)
    # Vectorized concatenation — a boxed-int Python list would cost ~28
    # bytes/token and dominate wall time on real (1e8+ token) corpora.
    pieces: list = []
    for d in docs:
        pieces.append(np.asarray(d, np.int32).ravel())
        pieces.append(eos)
    if not pieces:
        raise ValueError("no documents to pack")
    stream = np.concatenate(pieces)
    n = -(-len(stream) // seq)
    flat = np.full((n * seq,), pad_id, np.int32)
    flat[:len(stream)] = stream
    mask = np.zeros((n * seq,), np.float32)
    mask[:len(stream)] = 1.0
    return flat.reshape(n, seq), mask.reshape(n, seq)


def write_token_file(path: str, tokens: "np.ndarray",
                     dtype=np.uint16) -> str:
    """Write a flat token array as a ``.bin`` corpus (tooling/tests).
    Ids that overflow ``dtype`` fail loudly — uint16 wraps 128k-vocab ids
    silently otherwise."""
    arr = np.asarray(tokens)
    if arr.ndim != 1:
        raise ValueError(f"corpus must be flat, got shape {arr.shape}")
    info = np.iinfo(dtype)
    if arr.size and (arr.min() < info.min or arr.max() > info.max):
        raise ValueError(
            f"token ids [{arr.min()}, {arr.max()}] overflow {np.dtype(dtype)}"
            f" [{info.min}, {info.max}] — use dtype=np.uint32")
    arr.astype(dtype).tofile(path)
    return path
