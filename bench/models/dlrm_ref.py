"""Plain reference of the served DLRM-DCNv2 ranking path.

The published forward pass in `jax.numpy`, from the seeded inputs alone:
each table's rows remade from the seed block by block (`dlrm_gen`),
quantized row-wise to int8 with this module's own plain code, and kept
only where the checked requests look them up; each bag the sum of its
rows; the bottom MLP, DCNv2's low-rank cross network
(x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l), the top MLP, and the top-k
of each request's logits. No kernel, batching or serving code: it imports
nothing of the program and never reads the program's tables.

`precision` is the matmul precision of every product: ``"highest"`` for
the reference itself, ``"high"`` (three bf16 passes) for the control. A
bag's sum is its rows' product with a vector of ones, so at ``"high"``
each row enters it as the bf16 pair of those passes (hi, and the rest
rounded to bf16). The pair is rounded with integer ops: written as a
product with ones, the TPU compiler turns it into a float32 sum and drops
a plain bf16 conversion's rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import dlrm_gen as gen
from bench.models.youtubednn_ref import dequant, mm, quantize

CHUNK = 4  # requests pooled and ranked at a time


@functools.partial(jax.jit, static_argnames=("block", "d"))
def _block_rows(key, k, local, *, block, d):
    """The int8 values and scales of rows `local` of row block `k`."""
    q, s = quantize(gen.row_block(key, k, block=block, d=d))
    return q[local], s[local]


def _padded(local: np.ndarray) -> np.ndarray:
    """`local` padded with zeros to a power of two (at least 1024), so
    that few shapes compile."""
    n = max(1024, 1 << (len(local) - 1).bit_length())
    return np.concatenate([local, np.zeros(n - len(local), local.dtype)])


def _bf16(x):
    """float32 `x` rounded to bf16's 8 significand bits, with integer ops,
    kept in float32."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(
        (bits + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000), jnp.float32)


@functools.partial(jax.jit, static_argnames=("bags", "precision"))
def _forward(weights, rows, dense, *, bags, precision):
    """rows (S, C, slots, d) dequantized, dense (S, C, 13) -> pooled
    (S, C, tables, d) and logits (S, C)."""
    if precision == "high":
        hi = _bf16(rows)
        rows = hi + _bf16(rows - hi)
    pooled, lo = [], 0
    for n in bags:
        pooled.append(jnp.sum(rows[:, :, lo:lo + n], axis=2))
        lo += n
    x = dense
    for p in weights["bottom"]:
        x = jax.nn.relu(mm(x, p["w"], precision) + p["b"])
    x0 = jnp.concatenate([x] + pooled, axis=-1)
    x = x0
    for p in weights["cross"]:
        x = x0 * (mm(mm(x, p["V"], precision), p["W"], precision) + p["b"]) \
            + x
    top = weights["top"]
    for i, p in enumerate(top):
        x = mm(x, p["w"], precision) + p["b"]
        if i < len(top) - 1:
            x = jax.nn.relu(x)
    return jnp.stack(pooled, axis=2), x[..., 0]


class Reference:
    """The reference ranking path over the configuration's seeded inputs.

    Args:
      inputs: the build's `reference_inputs` (configuration, float
        weights).
      precision: ``"highest"`` (the reference) or ``"high"`` (control).
    """

    def __init__(self, inputs: dict, precision: str = "highest"):
        self.cfg = inputs["cfg"]
        self.weights = jax.tree.map(jnp.asarray, inputs["weights"])
        self.precision = precision
        bags, rows = self.cfg["bag_sizes"], self.cfg["held_rows"]
        starts = np.cumsum([0] + list(rows[:-1]))
        self.offsets = np.repeat(starts, bags)

    def _rows_for(self, gid: np.ndarray) -> tuple:
        """Remake, block by block, the int8 values and scales of the
        global row ids `gid` (sorted, unique)."""
        cfg = self.cfg
        block, d, key = cfg["row_block"], cfg["embed_dim"], gen.rows_key(cfg)
        q = np.zeros((len(gid), d), np.int8)
        s = np.zeros((len(gid), 1), np.float32)
        k_of = gid // block
        for k in np.unique(k_of):
            at = np.flatnonzero(k_of == k)
            qk, sk = _block_rows(key, int(k), _padded(gid[at] - k * block),
                                 block=block, d=d)
            q[at], s[at] = np.asarray(qk)[:len(at)], np.asarray(sk)[:len(at)]
        return q, s

    def serve(self, batch: dict) -> dict:
        """Reference outputs for a batch of requests (host arrays: dense
        (S, C, 13), sparse (S, C, slots)): pooled (S, C, tables, d),
        logits (S, C), items (S, top_k) candidate positions, scores
        (S, top_k) their logits."""
        sparse = np.asarray(batch["sparse"], np.int64) + self.offsets
        gid, at = np.unique(sparse, return_inverse=True)
        q, s = self._rows_for(gid)
        at = at.reshape(sparse.shape)
        pooled, logits = [], []
        for lo in range(0, len(sparse), CHUNK):
            sl = at[lo:lo + CHUNK]
            rows = dequant(jnp.asarray(q[sl]), jnp.asarray(s[sl]))
            p, lg = _forward(self.weights, rows,
                             jnp.asarray(batch["dense"][lo:lo + CHUNK]),
                             bags=tuple(self.cfg["bag_sizes"]),
                             precision=self.precision)
            pooled.append(np.asarray(p))
            logits.append(np.asarray(lg))
        logits = np.concatenate(logits)
        scores, items = jax.lax.top_k(jnp.asarray(logits), self.cfg["top_k"])
        return {"pooled": np.concatenate(pooled), "logits": logits,
                "items": np.asarray(items), "scores": np.asarray(scores)}

    def rescore(self, out: dict, items) -> np.ndarray:
        """The reference logit of each served candidate position."""
        return np.take_along_axis(out["logits"], np.asarray(items), 1)


def _scale(rms: np.ndarray) -> np.ndarray:
    """Each entry's RMS, or the median RMS where that is larger."""
    return np.maximum(rms, np.median(rms))


def numbers(prog: dict, items, scores, ref: dict, rescored) -> dict:
    """The compared numbers of one run.

    prog: what the timed path's stage programs give for the sampled
      requests (pooled, logits); items / scores: what the window served
      them; ref: `Reference.serve` of the same requests; rescored: the
      reference logit of each served candidate.

    * pooled_gap: the widest gap of a pooled bag's entry, over the larger
      of the bag's RMS and the median bag's;
    * logit_gap: the widest gap of a logit, over all candidates of the
      stage programs and over the served scores, and the widest amount by
      which a served candidate's reference logit lies below the
      reference's own at that rank (a wrong top-k; a near-tie swapped by
      rounding moves it by no more than the logits' own gaps), each over
      the larger of the request's logit RMS and the median request's;
    * item_miss: the share of served requests whose items differ, and
      rank_gap, the last term of logit_gap alone: readings for the
      record, which no limit checks (near-ties swap on rounding, so
      neither is a measure of its own).
    """
    pr = np.asarray(ref["pooled"], np.float32)
    bag_rms = _scale(np.sqrt(np.mean(pr.astype(np.float64) ** 2, -1)))
    pooled_gap = np.max(np.abs(np.asarray(prog["pooled"], np.float32) - pr)
                        / bag_rms[..., None].astype(np.float32))
    lr = np.asarray(ref["logits"], np.float64)
    req = _scale(np.sqrt(np.mean(lr ** 2, -1)))[:, None]
    below = (np.asarray(ref["scores"], np.float64) - rescored) / req
    rank_gap = np.max(np.maximum(below, 0.0))
    logit_gap = max(
        np.max(np.abs(np.asarray(prog["logits"], np.float64) - lr) / req),
        np.max(np.abs(np.nan_to_num(np.asarray(scores, np.float64)
                                    - rescored, nan=np.inf)) / req),
        rank_gap)
    return {
        "pooled_gap": float(pooled_gap),
        "logit_gap": float(logit_gap),
        "item_miss": float(np.mean((np.asarray(items)
                                    != ref["items"]).any(1))),
        "rank_gap": float(rank_gap),
    }
