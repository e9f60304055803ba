"""Plain reference of the served YoutubeDNN path (iMARS Fig. 3).

The same semantics as the program's staged serve steps, written out in
`jax.numpy` from the seeded float inputs alone: row-wise int8 tables,
the filtering MLP, 256-bit sign-random-projection signatures, a full
fixed-radius Hamming scan over every live row (tombstones and pending
rows applied), candidates ordered by (distance, id), the ranking MLP and
the top-k by CTR. No kernel, cache, summary, batching or serving code:
it imports nothing of the program.

`precision` is the matmul precision of every product: ``"highest"`` for
the reference itself, ``"high"`` (three bf16 passes, the product of the
two low halves dropped) for the control, spelled out so that it means the
same on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import youtubednn_gen as gen

WORD = 32
ID_BITS = 24  # candidate keys pack (distance << 24 | id) into an int32
NO_KEY = np.int32(2 ** 31 - 1)
SCAN_ROWS = 1 << 17  # base rows per scan block


def _split(x):
    """x = hi + lo: hi is x rounded to 8 significand bits (with integer
    ops, so that no compiler can drop the rounding as excess precision),
    lo the rest rounded to bf16."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(
        (bits + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _operands(a, b, precision):
    """The bf16 pass operands of a product at `precision`."""
    if precision == "highest":
        return None
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (ah, bh), (ah, bl), (al, bh)


def mm(a, b, precision: str):
    """a @ b at `precision`."""
    passes = _operands(a, b, precision)
    if passes is None:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    hh, hl, lh = (jnp.matmul(x, y, preferred_element_type=jnp.float32)
                  for x, y in passes)
    return hh + (hl + lh)


def pool(rows, w, precision: str):
    """(B, L, d) rows weighted by (B, L) -> (B, d), at `precision`."""
    passes = _operands(rows, w, precision)
    spec = "bld,bl->bd"
    if passes is None:
        return jnp.einsum(spec, rows, w, precision=jax.lax.Precision.HIGHEST)
    hh, hl, lh = (jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)
                  for x, y in passes)
    return hh + (hl + lh)


def quantize(x):
    """Row-wise symmetric int8: (values int8, scales (n, 1) f32)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequant(q, s):
    return q.astype(jnp.float32) * s


def signature(x, proj, precision):
    """(..., d) -> (..., bits / 32) uint32: bit j of word w is
    [x @ proj[:, 32 w + j] >= 0]."""
    bits = (mm(x, proj, precision) >= 0.0).astype(jnp.uint32)
    bits = bits.reshape(*bits.shape[:-1], -1, WORD)
    return jnp.sum(bits << jnp.arange(WORD, dtype=jnp.uint32), -1,
                   dtype=jnp.uint32)


def mlp(layers, x, precision):
    for i, p in enumerate(layers):
        x = mm(x, p["w"], precision) + p["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


@functools.partial(jax.jit, static_argnames=("radius", "k", "precision"))
def _scan_block(qsig, rows, ids, alive, proj, *, radius, k, precision):
    """Candidate keys (S, k) and within-radius counts (S,) of one block."""
    sig = signature(rows, proj, precision)
    d = jnp.sum(jax.lax.population_count(qsig[:, None, :] ^ sig[None]), -1,
                dtype=jnp.int32)
    hit = (d <= radius) & alive[None]
    key = jnp.where(hit, (d << ID_BITS) | ids[None], NO_KEY)
    neg, _ = jax.lax.top_k(-key, k)
    return -neg, jnp.sum(hit, -1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(keys, k):
    neg, _ = jax.lax.top_k(-keys, k)
    return -neg


class Reference:
    """The reference serving path over one configuration's seeded inputs.

    Args:
      inputs: the build's `reference_inputs` (float weights, LSH key,
        catalog plan, update batch, serving knobs).
      precision: ``"highest"`` (the reference) or ``"high"`` (control).
    """

    def __init__(self, inputs: dict, precision: str = "highest"):
        self.x = inputs
        self.precision = precision
        p = jax.tree.map(jnp.asarray, inputs["params"])
        self.params = p
        self.proj = jax.random.normal(jax.random.key(inputs["lsh_seed"]),
                                      (p["item_table"].shape[1],
                                       inputs["lsh_bits"]), jnp.float32)
        self.tables = {k: dequant(*quantize(v))
                       for k, v in p["tables"].items()}
        self.genre = dequant(*quantize(p["genre_table"]))
        self.n_items = int(inputs["n_items"])
        if self.n_items + len(inputs["upsert_ids"]) >= 1 << ID_BITS:
            raise ValueError("catalog too large for packed candidate keys")
        if not 0 <= inputs["radius"] < 1 << (31 - ID_BITS):
            raise ValueError("radius too large for packed candidate keys")
        up_q, up_s = quantize(jnp.asarray(inputs["upsert_rows"]))
        self.up_rows = dequant(up_q, up_s)
        self.up_ids = np.asarray(inputs["upsert_ids"], np.int64)
        alive = np.ones(self.n_items, bool)
        alive[np.asarray(inputs["delete_ids"], np.int64)] = False
        alive[self.up_ids[self.up_ids < self.n_items]] = False
        self.alive = alive
        self._base_q = self._base_s = None

    # -- the catalog ------------------------------------------------------
    def _base_blocks(self):
        """Yield (lo, float rows) over the base catalog in scan blocks."""
        x = self.x
        trained = self.params["item_table"]
        n0 = trained.shape[0]
        for lo in range(0, n0, SCAN_ROWS):
            yield lo, trained[lo:lo + SCAN_ROWS]
        if self.n_items > n0:
            centers = jnp.asarray(x["centers"])
            key = jax.random.key(x["catalog_seed"])
            per, los = gen.catalog_plan(n0, self.n_items, len(x["centers"]),
                                        x["catalog_chunk"])
            for lo in los:
                rows = gen.tail_rows(centers, key, lo,
                                     chunk=x["catalog_chunk"],
                                     per_cluster=per)
                hi = min(x["catalog_chunk"], self.n_items - n0 - lo)
                for b in range(0, hi, SCAN_ROWS):
                    yield n0 + lo + b, rows[b:min(b + SCAN_ROWS, hi)]

    def _rows(self, ids):
        """Dequantized current rows of item `ids` (pending rows first,
        then the base table; ids outside both read zero)."""
        ids = np.asarray(ids, np.int64)
        out = np.zeros(ids.shape + (self.up_rows.shape[1],), np.float32)
        base = (ids >= 0) & (ids < self.n_items)
        out[base] = np.asarray(dequant(self._base_q[ids[base]],
                                       self._base_s[ids[base]]))
        up = np.asarray(self.up_rows)
        for j, gid in enumerate(self.up_ids):
            out[ids == gid] = up[j]
        return jnp.asarray(out)

    # -- serving ----------------------------------------------------------
    def serve(self, batch: dict) -> dict:
        """Reference outputs for a batch of queries (host arrays: one (S,)
        array per user feature, ``history`` (S, H), ``genre`` (S,)).

        Returns u (S, d), qsig (S, words), cand / dist (S, K) (-1 / -1
        padded), counts (S,), items (S, top_k) (-1 padded), scores
        (S, top_k) (-inf padded), and `ctx` for `rescore`.
        """
        x, prec = self.x, self.precision
        if self._base_q is None:
            parts = [quantize(r) for _, r in self._base_blocks()]
            self._base_q = jnp.concatenate([q for q, _ in parts])
            self._base_s = jnp.concatenate([s for _, s in parts])
        hist = np.asarray(batch["history"])
        valid = (hist >= 0).astype(np.float32)
        pooled = pool(self._rows(hist), jnp.asarray(valid), prec) \
            / jnp.maximum(jnp.asarray(valid).sum(1, keepdims=True), 1.0)
        feats = [self.tables[k][np.asarray(batch[k])]
                 for k in sorted(self.tables)]
        u = mlp(self.params["filter_mlp"],
                jnp.concatenate(feats + [pooled], -1), prec)
        qsig = signature(u, self.proj, prec)

        k = int(x["n_candidates"])
        keys, counts = [], 0
        for lo in range(0, self.n_items, SCAN_ROWS):
            hi = min(lo + SCAN_ROWS, self.n_items)
            block = dequant(self._base_q[lo:hi], self._base_s[lo:hi])
            key, c = _scan_block(qsig, block, jnp.arange(lo, hi),
                                 jnp.asarray(self.alive[lo:hi]), self.proj,
                                 radius=int(x["radius"]), k=k, precision=prec)
            keys.append(key)
            counts = counts + c
        if len(self.up_ids):
            key, c = _scan_block(
                qsig, self.up_rows, jnp.asarray(self.up_ids, jnp.int32),
                jnp.ones(len(self.up_ids), bool), self.proj,
                radius=int(x["radius"]), k=min(k, len(self.up_ids)),
                precision=prec)
            keys.append(key)
            counts = counts + c
        best = np.asarray(_merge(jnp.concatenate(keys, 1), k))
        ok = best != NO_KEY
        cand = np.where(ok, best & ((1 << ID_BITS) - 1), -1)
        dist = np.where(ok, best >> ID_BITS, -1)

        genre = self.genre[np.asarray(batch["genre"])]
        ctx = jnp.concatenate([u, genre, pooled], -1)
        ctr = self._ctr(ctx, cand)
        vals, idx = jax.lax.top_k(ctr, int(x["top_k"]))
        vals, idx = np.asarray(vals), np.asarray(idx)
        items = np.where(np.isfinite(vals),
                         np.take_along_axis(cand, idx, 1), -1)
        return {"u": np.asarray(u), "qsig": np.asarray(qsig), "cand": cand,
                "dist": dist, "counts": np.asarray(counts), "items": items,
                "scores": vals, "ctx": ctx}

    def _ctr(self, ctx, ids):
        """Sigmoid CTR of each (query, item id) pair; -inf where id < 0."""
        ids = np.asarray(ids)
        rows = self._rows(ids)
        s, n = ids.shape
        xin = jnp.concatenate(
            [jnp.broadcast_to(ctx[:, None], (s, n, ctx.shape[-1])), rows], -1)
        logit = mlp(self.params["rank_mlp"], xin, self.precision)[..., 0]
        return jnp.where(jnp.asarray(ids) >= 0, jax.nn.sigmoid(logit),
                         -jnp.inf)

    def rescore(self, out: dict, items) -> np.ndarray:
        """The reference CTR of each served item (-inf where item < 0)."""
        return np.asarray(self._ctr(out["ctx"], items))


def numbers(prog: dict, items, scores, ref: dict, rescored) -> dict:
    """The compared numbers of one run.

    prog: what the timed path's stage programs give for the sampled
      queries (u, cand, dist, counts); items / scores: what the window
      served them; ref: `Reference.serve` of the same queries; rescored:
      the reference CTR of each served item.

    * u_gap: the widest gap of a user-embedding entry, over the larger of
      its row's RMS and the median row's RMS;
    * cand_miss: the share of queries whose candidates (ids and distances)
      or match count differ from the reference's;
    * count_gap: the widest relative gap of a match count;
    * item_miss: the share of served requests whose items differ;
    * score_gap: the widest gap between a served score and the reference
      CTR of the item served (1 where an item is missing on one side);
    * rank_gap: the widest amount by which a served item's reference CTR
      lies below the reference's own at that rank (its score where the
      served list ran short).
    """
    u, ur = np.asarray(prog["u"], np.float64), np.asarray(ref["u"], np.float64)
    rms = np.sqrt(np.mean(ur ** 2, 1))
    scale = np.maximum(rms, np.median(rms))[:, None]
    cand_ok = ((prog["cand"] == ref["cand"])
               & ((ref["cand"] < 0) | (prog["dist"] == ref["dist"]))).all(1)
    cand_ok &= prog["counts"] == ref["counts"]
    served = items >= 0
    want = ref["items"] >= 0
    gap = np.where(served, np.abs(np.nan_to_num(scores - rescored,
                                                nan=1.0, posinf=1.0,
                                                neginf=1.0)), 0.0)
    gap = np.where(served != want, 1.0, gap)
    below = np.where(want, ref["scores"] - np.where(served, rescored, 0.0),
                     0.0)
    return {
        "u_gap": float(np.max(np.abs(u - ur) / scale)),
        "cand_miss": float(np.mean(~cand_ok)),
        "count_gap": float(np.max(np.abs(prog["counts"] - ref["counts"])
                                  / np.maximum(ref["counts"], 1))),
        "item_miss": float(np.mean((items != ref["items"]).any(1))),
        "score_gap": float(np.max(gap)),
        "rank_gap": float(np.max(np.maximum(below, 0.0))),
    }
