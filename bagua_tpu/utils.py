"""Small utilities: pytree flatten helpers, dtype mapping, rate tracking.

Counterpart of reference ``bagua/torch_api/utils.py`` (flatten/unflatten :10-54,
to_bagua_datatype :205, StatisticalAverage :251-368).  Flattening here operates
on JAX pytrees instead of torch tensor lists; the fused-param-storage helpers
(`flatten_module_params`) have no TPU analog because XLA owns layout — the
bucket layer (bagua_tpu/bucket.py) is the equivalent mechanism.
"""

from __future__ import annotations

import logging
import math
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .define import TensorDtype


def to_bagua_datatype(dtype) -> TensorDtype:
    """jnp/np dtype -> wire datatype name (reference utils.py:205-216)."""
    d = jnp.dtype(dtype)
    if d == jnp.float32:
        return TensorDtype.F32
    if d == jnp.float16:
        return TensorDtype.F16
    if d == jnp.bfloat16:
        return TensorDtype.BF16
    if d == jnp.uint8:
        return TensorDtype.U8
    if d == jnp.int32:
        return TensorDtype.I32
    if d == jnp.int64:
        return TensorDtype.I64
    raise ValueError(f"unsupported data type {dtype}.")


def from_bagua_datatype(dtype: TensorDtype):
    return {
        TensorDtype.F32: jnp.float32,
        TensorDtype.F16: jnp.float16,
        TensorDtype.BF16: jnp.bfloat16,
        TensorDtype.U8: jnp.uint8,
        TensorDtype.I32: jnp.int32,
        TensorDtype.I64: jnp.int64,
    }[TensorDtype(dtype)]


def flatten(arrays: List[jax.Array]) -> jax.Array:
    """Concatenate arrays into one flat 1-D buffer (reference utils.py:10-25)."""
    if len(arrays) == 0:
        return jnp.zeros((0,), dtype=jnp.float32)
    return jnp.concatenate([jnp.ravel(a) for a in arrays])


def unflatten(flat: jax.Array, like: List[jax.Array]) -> List[jax.Array]:
    """Split a flat buffer back into arrays shaped like ``like``
    (reference utils.py:28-43)."""
    outs = []
    offset = 0
    for a in like:
        n = a.size
        outs.append(jax.lax.dynamic_slice_in_dim(flat, offset, n).reshape(a.shape))
        offset += n
    return outs


def check_contiguous(sizes: List[int], offsets: List[int]) -> bool:
    off = 0
    for s, o in zip(sizes, offsets):
        if o != off:
            return False
        off += s
    return True


def apply_flattened_call(tree, call):
    leaves, treedef = jax.tree.flatten(tree)
    flat = flatten(leaves)
    flat = call(flat)
    return jax.tree.unflatten(treedef, unflatten(flat, leaves))


def average_by_removing_extreme_values(raw_score_list):
    """Robust mean: drop values > 3 sigma from the median-ish mean, like the
    reference's speed averaging (utils.py:219-248)."""
    score_list = np.asarray(raw_score_list, dtype=np.float64)
    while len(score_list) > 2:
        mean = score_list.mean()
        std = score_list.std()
        keep = np.abs(score_list - mean) <= 3 * std
        if keep.all():
            break
        score_list = score_list[keep]
    return float(score_list.mean()), float(score_list.std()), score_list.tolist()


class StatisticalAverage:
    """Exponentially time-bucketed rate tracker (reference utils.py:251-368).

    Records a cumulative value (e.g. samples processed) at wall-clock times and
    answers "average rate over the last T seconds" with power-of-two bucketing.
    """

    def __init__(self, last_update_time: float = None, records: List[float] = None,
                 record_tail: Tuple[float, float] = (0.0, 0.0)):
        self.last_update_time = time.time() if last_update_time is None else last_update_time
        self.records: List[float] = list(records) if records else []
        self.record_tail = record_tail

    def record_seconds(self) -> float:
        # buckets of 1, 2, 4, ... 2^(L-1) seconds cover 2^L - 1 seconds.
        # Claiming 2^L here would self-inflate: record()'s regrow loop runs
        # while 2^i <= total + elapsed, so an overcount of exactly one
        # second makes EVERY call grow the list by one bucket regardless of
        # elapsed time — unbounded, and 2.0 ** i overflows after ~1000
        # steps of training
        return 2.0 ** len(self.records) - 1.0 if self.records else 0.0

    def total_recording_time(self) -> float:
        tail_sec, _ = self.record_tail
        return self.record_seconds() + tail_sec

    def get_records_mean(self, last_n_seconds: float) -> float:
        if last_n_seconds <= 0:
            return 0.0
        records_seconds = self.record_seconds()
        tail_seconds, tail_mean = self.record_tail
        if len(self.records) == 0:
            return tail_mean
        if last_n_seconds < 1.0:
            return self.records[0]
        if last_n_seconds <= records_seconds:
            mean = 0.0
            cnt = int(math.floor(math.log2(last_n_seconds)))
            for i in range(cnt):
                mean += (2.0 ** i / last_n_seconds) * self.records[i]
            last_sec = last_n_seconds - 2.0 ** cnt + (2.0 ** cnt - sum(2.0 ** i for i in range(cnt)))
            mean += max(last_sec, 0.0) / last_n_seconds * self.records[min(cnt, len(self.records) - 1)]
            return mean
        mean = (records_seconds / max(last_n_seconds, 1e-9)) * (
            sum(2.0 ** i * r for i, r in enumerate(self.records)) / max(records_seconds, 1e-9)
        )
        remain = min(last_n_seconds - records_seconds, tail_seconds)
        mean += (remain / max(last_n_seconds, 1e-9)) * tail_mean
        return mean

    def record(self, val: float):
        if not math.isfinite(val):
            return  # a zero-dt window's inf rate would poison every mean
        now = time.time()
        elapsed = now - self.last_update_time
        new_records: List[float] = []
        total = self.total_recording_time()
        i = 0
        while 2.0 ** i <= total + elapsed:
            seconds = 2.0 ** i
            if seconds <= elapsed:
                new_records.append(val)
            else:
                mean = (elapsed / seconds) * val + ((seconds - elapsed) / seconds) * self.get_records_mean(seconds - elapsed)
                new_records.append(mean)
            i += 1
        tail_total = min(total + elapsed, 2.0 ** 10)
        tail_sec = max(tail_total - (2.0 ** (len(new_records)) - 1 if new_records else 0), 0.0)
        tail_mean = self.get_records_mean(tail_total) if tail_sec > 0 else 0.0
        self.records = new_records
        self.record_tail = (tail_sec, tail_mean)
        self.last_update_time = now

    def get(self, last_n_seconds: float) -> float:
        elapsed = time.time() - self.last_update_time
        if elapsed >= last_n_seconds:
            return 0.0
        return self.get_records_mean(last_n_seconds - elapsed) * (
            (last_n_seconds - elapsed) / last_n_seconds
        )

    def total(self) -> float:
        total_sec = self.total_recording_time()
        return self.get_records_mean(total_sec) * total_sec


def lru_get_or_build(cache: dict, max_entries: int, key, build):
    """The bounded insertion-ordered LRU idiom shared by the compiled-
    program caches (``models.generate``'s signature caches,
    ``serve.engine``'s program cache): pop-on-hit + re-insert moves the
    entry to most-recent, ``build()`` fills a miss, and eviction drops the
    oldest entries beyond ``max_entries`` (an evicted program just
    recompiles on its next use)."""
    value = cache.pop(key, None)
    if value is None:
        value = build()
    cache[key] = value
    while len(cache) > max_entries:
        cache.pop(next(iter(cache)))
    return value


def find_free_port(low: int = 20000, high: int = 65000) -> int:
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


logger = logging.getLogger("bagua_tpu")


def remat_wrap(block_cls, remat_policy=None, matmul_names=()):
    """Wrap a flax module class in ``nn.checkpoint`` with a NAMED policy —
    the single source of the policy-name map shared by the transformer and
    ResNet ``remat``/``remat_policy`` knobs.  What the backward pass finds
    kept, per policy:

    - ``None``: the block's input only; everything is recomputed.
    - ``"dots"``: every ``dot_general`` output, and the flash-attention
      kernel's ``o`` and ``lse`` (two matmuls in one ``pallas_call``, which
      the dots rule cannot see: they are kept by their ``checkpoint_name``
      tags, ``ops.flash_attention.KEPT_O`` / ``KEPT_LSE``).
    - ``"dots_no_batch"``: ``dot_general`` outputs without batch dims, and
      ``o`` / ``lse``.  A block that tags its own matmul outputs passes the
      tags as ``matmul_names``, and then keeps exactly the named values: a
      matmul it left untagged (the transformer's attention out-projection,
      as large as the ``o`` it reads) is rebuilt in the replay.

    Tags nobody wrote are inert (ResNet blocks; attention off the kernel).
    """
    import flax.linen as nn

    from .ops.flash_attention import KEPT_LSE, KEPT_O

    if remat_policy is None:
        return nn.checkpoint(block_cls, policy=None)
    cp = jax.checkpoint_policies
    dots_rule = {
        "dots": cp.dots_saveable,
        "dots_no_batch": cp.dots_with_no_batch_dims_saveable,
    }[remat_policy]
    names = cp.save_only_these_names(KEPT_O, KEPT_LSE, *matmul_names)
    if matmul_names and remat_policy == "dots_no_batch":
        policy = names
    else:
        policy = cp.save_from_both_policies(dots_rule, names)
    return nn.checkpoint(block_cls, policy=policy)
