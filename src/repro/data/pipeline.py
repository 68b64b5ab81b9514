"""Deterministic synthetic data pipeline with host sharding + prefetch.

At 1000+ node scale the data layer must be (a) deterministic per (step,
host) so restarts and elastic re-meshes reproduce the same stream, (b)
host-sharded so no host materializes the global batch, and (c) prefetched
so input never serializes against the step.  This module provides all
three for the synthetic LM stream used by the examples/benchmarks; a real
corpus reader would only replace ``_tokens_for``.
"""
from __future__ import annotations

import queue
import threading
import time
import zlib

import numpy as np
import jax
import jax.numpy as jnp


class SyntheticLMStream:
    """Deterministic tokens: tokens[step, i, t] = hash(step, i, t) % vocab."""

    def __init__(self, vocab_size: int, global_batch: int, seq_len: int,
                 *, seed: int = 0, extras: dict | None = None):
        self.vocab = vocab_size
        self.B = global_batch
        self.S = seq_len
        self.seed = seed
        self.extras = extras or {}

    def _tokens_for(self, step: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, step))
        return rng.integers(0, self.vocab, (self.B, self.S), dtype=np.int32)

    def batch(self, step: int, *, train: bool = True) -> dict:
        tok = self._tokens_for(step)
        out = {"tokens": tok}
        if train:
            out["labels"] = np.roll(tok, -1, axis=1)
        for name, (sds, _spec) in self.extras.items():
            # stable digest, NOT hash(): str hashing is salted per process
            # (PYTHONHASHSEED), which would break the determinism contract
            # across restarts / elastic re-meshes.
            rng = np.random.default_rng(
                (self.seed, step, zlib.crc32(name.encode("utf-8"))))
            out[name] = rng.standard_normal(sds.shape).astype(sds.dtype)
        return out


class Prefetcher:
    """Background-thread prefetch of device-put batches."""

    def __init__(self, stream: SyntheticLMStream, shardings: dict,
                 start_step: int = 0, depth: int = 2, train: bool = True):
        self.stream = stream
        self.shardings = shardings
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._step = start_step
        self._exc: BaseException | None = None
        self.train = train
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        step = self._step
        try:
            while not self._stop.is_set():
                host = self.stream.batch(step, train=self.train)
                dev = {k: jax.device_put(v, self.shardings[k])
                       for k, v in host.items() if k in self.shardings}
                # a full queue keeps this step's batch: it is made once
                while True:
                    try:
                        self.q.put((step, dev), timeout=1.0)
                        break
                    except queue.Full:
                        if self._stop.is_set():
                            return
                step += 1
        except BaseException as e:  # propagate to the consumer, don't die mute
            self._exc = e

    def next(self, timeout: float = 60.0):
        """Blocking get that re-raises a producer-thread failure promptly
        instead of stalling for the full timeout and surfacing queue.Empty."""
        deadline = time.monotonic() + timeout
        while True:
            if self._exc is not None and self.q.empty():
                # sticky: the producer thread is dead, every subsequent
                # next() must surface the same root cause, not a timeout
                raise self._exc
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"prefetcher produced no batch within {timeout:.1f}s")
            try:
                return self.q.get(timeout=min(0.2, remaining))
            except queue.Empty:
                continue

    def stop(self):
        self._stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
