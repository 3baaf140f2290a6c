"""SequenceVectors / Word2Vec: skip-gram + CBOW with negative sampling and
hierarchical softmax.

Reference analog: models/sequencevectors/SequenceVectors.java (fit:192,
Hogwild VectorCalculationsThread pool :292-296), models/embeddings/learning/
impl/elements/SkipGram.java (:271-283 — the hot loop batches into the C++
AggregateSkipGram kernel), CBOW.java, InMemoryLookupTable.java
(syn0/syn1/expTable) in /root/reference/deeplearning4j-nlp-parent/
deeplearning4j-nlp.

TPU-native redesign: the Hogwild thread pool + native batched kernel become a
single jitted step over large batches of (center, context, negatives) index
arrays. Forward = gather (jnp.take), update = closed-form SGNS gradients
applied with scatter-add (.at[].add) — both native XLA TPU ops. Exact
semantics notes:
- negative sampling: unigram^0.75 table like the reference;
- subsampling of frequent words: p_discard = 1 - sqrt(t/f) like word2vec;
- dynamic window: b ~ U[1, window] per center, like the reference;
- hierarchical softmax: per-word Huffman codes/points padded to max depth,
  sigmoid updates along the path — same math, batched dense.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.utils.hostsync import fetch_losses
from deeplearning4j_tpu.text.vocab import (VocabCache, VocabConstructor,
                                           flatten_corpus)


class AliasTable:
    """Walker's alias method: O(n) build, O(1) sampling from a discrete
    distribution. Replaces np.random.choice(p=unigram^0.75) — which re-scans
    the whole vocab per batch — as the host-side analog of the reference's
    precomputed negative-sampling table (InMemoryLookupTable.java table/
    makeTable)."""

    def __init__(self, probs):
        probs = np.asarray(probs, np.float64)
        n = len(probs)
        scaled = probs * n / probs.sum()
        self.prob = np.zeros(n, np.float64)
        self.alias = np.zeros(n, np.int64)
        small = [i for i in range(n) if scaled[i] < 1.0]
        large = [i for i in range(n) if scaled[i] >= 1.0]
        while small and large:
            s, l = small.pop(), large.pop()
            self.prob[s] = scaled[s]
            self.alias[s] = l
            scaled[l] -= 1.0 - scaled[s]
            (small if scaled[l] < 1.0 else large).append(l)
        for i in small + large:
            self.prob[i] = 1.0

    def draw(self, rs, shape):
        idx = rs.randint(0, len(self.prob), size=shape)
        accept = rs.random_sample(np.shape(idx)) < self.prob[idx]
        return np.where(accept, idx, self.alias[idx]).astype(np.int32)


@functools.partial(jax.jit, static_argnums=(3,))
def _alias_draw_chunk(prob, alias, key, shape):
    """Device-side alias draw (same method as AliasTable.draw, jitted).
    Fixed ``shape`` per compile — callers draw in constant-size chunks so
    the varying per-epoch pair count never triggers a recompile."""
    k1, k2 = jax.random.split(key)
    idx = jax.random.randint(k1, shape, 0, prob.shape[0], dtype=jnp.int32)
    accept = jax.random.uniform(k2, shape) < prob[idx]
    return jnp.where(accept, idx, alias[idx])


def _scatter_mean_update(table, idx, grads, lr, axis=None):
    """Apply -lr * (per-row MEAN of grads) at idx. With unique indices this
    equals per-pair SGD; under collisions (small vocab / large batch) it stays
    stable where a raw scatter-ADD would multiply the step by the collision
    count and diverge (the reference's Hogwild applies pairs one at a time).

    ``axis``: inside shard_map, all_gather the (idx, grads) pairs over the
    mesh axis first, then scatter the GLOBAL batch locally — every device
    applies the identical update, equal to the single-device update over the
    global batch. Communication is O(batch * dim), independent of vocab size
    (a psum of the dense tables would be O(vocab * dim) per step)."""
    if axis is not None:
        idx = jax.lax.all_gather(idx, axis, tiled=True)
        grads = jax.lax.all_gather(grads, axis, tiled=True)
    num = jnp.zeros_like(table).at[idx].add(grads)
    cnt = jnp.zeros(table.shape[0], grads.dtype).at[idx].add(1.0)
    return table - lr * num / jnp.maximum(cnt, 1.0)[:, None]


def _sgns_core(gather0, gather1, scatter0, scatter1, centers, contexts,
               negatives):
    """Shared SGNS forward/gradient/loss math, parametrized over table
    access: ``gather0/gather1`` read rows of syn0/syn1, ``scatter0/
    scatter1`` apply the mean-scatter update. Both the replicated-table
    path (_sgns_math) and the vocab-sharded path
    (_sgns_math_table_sharded) are thin wrappers, so their pinned
    exactness cannot drift apart.

    Closed-form gradients of  -log σ(v·u+) - Σ log σ(-v·u-)  applied via
    scatter updates (the XLA-native replacement for AggregateSkipGram)."""
    v = gather0(centers)                           # [B,D]
    u_pos = gather1(contexts)                      # [B,D]
    u_neg = gather1(negatives)                     # [B,K,D]

    s_pos = jax.nn.sigmoid(jnp.einsum("bd,bd->b", v, u_pos))          # [B]
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", v, u_neg))        # [B,K]

    g_pos = (s_pos - 1.0)[:, None]                 # d/du+ coefficient
    g_neg = s_neg[..., None]                       # d/du- coefficient

    grad_v = g_pos * u_pos + jnp.einsum("bk,bkd->bd", s_neg, u_neg)
    grad_u_pos = g_pos * v
    grad_u_neg = g_neg * v[:, None, :]

    syn0 = scatter0(centers, grad_v)
    u_idx = jnp.concatenate([contexts, negatives.reshape(-1)])
    u_grads = jnp.concatenate([grad_u_pos,
                               grad_u_neg.reshape(-1, grad_u_neg.shape[-1])])
    syn1neg = scatter1(u_idx, u_grads)

    loss = -jnp.mean(jnp.log(jnp.clip(s_pos, 1e-9, 1.0))
                     + jnp.sum(jnp.log(jnp.clip(1.0 - s_neg, 1e-9, 1.0)),
                               axis=1))
    return syn0, syn1neg, loss


def _sgns_math(syn0, syn1neg, centers, contexts, negatives, lr, axis=None):
    """One batched skip-gram negative-sampling update (replicated tables).

    centers [B], contexts [B], negatives [B,K]; returns (syn0, syn1neg,
    loss)."""
    syn0, syn1neg, loss = _sgns_core(
        lambda idx: jnp.take(syn0, idx, axis=0),
        lambda idx: jnp.take(syn1neg, idx, axis=0),
        lambda idx, g: _scatter_mean_update(syn0, idx, g, lr, axis),
        lambda idx, g: _scatter_mean_update(syn1neg, idx, g, lr, axis),
        centers, contexts, negatives)
    if axis is not None:
        loss = jax.lax.pmean(loss, axis)
    return syn0, syn1neg, loss


def _hs_math(syn0, syn1, centers, points, codes, path_mask, lr, axis=None):
    """Hierarchical-softmax skip-gram update.

    points/codes/path_mask: [B, L] padded Huffman paths. Loss:
    -Σ log σ((1-2*code) * v·u_point).
    """
    v = jnp.take(syn0, centers, axis=0)            # [B,D]
    u = jnp.take(syn1, points, axis=0)             # [B,L,D]
    sign = 1.0 - 2.0 * codes                       # code 0 -> +1, 1 -> -1
    dot = jnp.einsum("bd,bld->bl", v, u)
    s = jax.nn.sigmoid(sign * dot)
    g = (s - 1.0) * sign * path_mask               # [B,L]

    grad_v = jnp.einsum("bl,bld->bd", g, u)
    grad_u = g[..., None] * v[:, None, :]

    syn0 = _scatter_mean_update(syn0, centers, grad_v, lr, axis)
    syn1 = _scatter_mean_update(syn1, points.reshape(-1),
                                grad_u.reshape(-1, grad_u.shape[-1]), lr,
                                axis)
    loss = -jnp.sum(jnp.log(jnp.clip(s, 1e-9, 1.0)) * path_mask) / \
        jnp.maximum(jnp.sum(path_mask), 1.0)
    if axis is not None:
        loss = jax.lax.pmean(loss, axis)
    return syn0, syn1, loss


def _cbow_math(syn0, syn1neg, context_idx, context_mask, targets, negatives, lr,
               axis=None):
    """CBOW-NS: mean of context vectors predicts the target (reference: CBOW.java)."""
    ctx = jnp.take(syn0, context_idx, axis=0)      # [B,W,D]
    m = context_mask[..., None]
    h = jnp.sum(ctx * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)  # [B,D]
    u_pos = jnp.take(syn1neg, targets, axis=0)
    u_neg = jnp.take(syn1neg, negatives, axis=0)
    s_pos = jax.nn.sigmoid(jnp.einsum("bd,bd->b", h, u_pos))
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, u_neg))
    g_pos = (s_pos - 1.0)[:, None]
    grad_h = g_pos * u_pos + jnp.einsum("bk,bkd->bd", s_neg, u_neg)
    counts = jnp.maximum(jnp.sum(context_mask, axis=1, keepdims=True), 1.0)
    grad_ctx = (grad_h[:, None, :] / counts[..., None]) * m
    # mask padded slots to index 0 with zero gradient (mean-normalized scatter)
    syn0 = _scatter_mean_update(syn0, context_idx.reshape(-1),
                                grad_ctx.reshape(-1, grad_ctx.shape[-1]), lr,
                                axis)
    u_idx = jnp.concatenate([targets, negatives.reshape(-1)])
    u_grads = jnp.concatenate([
        g_pos * h, (s_neg[..., None] * h[:, None, :]).reshape(-1, h.shape[-1])])
    syn1neg = _scatter_mean_update(syn1neg, u_idx, u_grads, lr, axis)
    loss = -jnp.mean(jnp.log(jnp.clip(s_pos, 1e-9, 1.0))
                     + jnp.sum(jnp.log(jnp.clip(1.0 - s_neg, 1e-9, 1.0)), axis=1))
    if axis is not None:
        loss = jax.lax.pmean(loss, axis)
    return syn0, syn1neg, loss


def _epoch_body(math_fn):
    """Whole-epoch scan body over stacked batches (shared by the jitted
    single-device path and the shard_map'd distributed path)."""
    def epoch(syn0, syn1, batches, lr):
        def body(carry, batch):
            s0, s1, loss = math_fn(*carry, *batch, lr)
            return (s0, s1), loss
        (syn0, syn1), losses = jax.lax.scan(body, (syn0, syn1), batches)
        return syn0, syn1, losses
    return epoch


def _epoch_scan(math_fn):
    """Wrap a per-batch update into a whole-epoch lax.scan: all full batches
    execute inside ONE jitted computation, eliminating per-step dispatch +
    host sync (the role of the reference's Hogwild thread pool feeding the
    native batched kernel, SequenceVectors.java:292-296)."""
    return functools.partial(jax.jit, donate_argnums=(0, 1))(
        _epoch_body(math_fn))


# per-batch jitted steps (tail batches, tests) + whole-epoch scans
_sgns_step = functools.partial(jax.jit, donate_argnums=(0, 1))(_sgns_math)
_hs_step = functools.partial(jax.jit, donate_argnums=(0, 1))(_hs_math)
_cbow_step = functools.partial(jax.jit, donate_argnums=(0, 1))(_cbow_math)
_sgns_epoch = _epoch_scan(_sgns_math)
_hs_epoch = _epoch_scan(_hs_math)
_cbow_epoch = _epoch_scan(_cbow_math)


def _dist_fns(math_fn, mesh):
    """shard_map'd (step, epoch) pair: index batches shard over the mesh
    ``data`` axis, embedding tables stay replicated, and the kernels
    all_gather (idx, grads) pairs before scattering — every device applies
    the identical update, equal to the single-device update over the global
    batch, with O(batch * dim) traffic per step.

    Reference analog: dl4j-spark-nlp Word2Vec (spark/dl4j-spark-nlp/.../
    Word2Vec.java — per-epoch parameter averaging over Spark workers). The
    TPU redesign pools gradients every BATCH over ICI instead of averaging
    parameters every EPOCH over the driver, which is exact rather than
    approximate.
    """
    from jax.sharding import PartitionSpec as P

    axis_math = functools.partial(math_fn, axis="data")

    def step(syn0, syn1, *rest):
        batch, lr = rest[:-1], rest[-1]
        return axis_math(syn0, syn1, *batch, lr)

    epoch = _epoch_body(axis_math)

    def make(fn, scan_dim):
        def sharded(syn0, syn1, *rest):
            batch, lr = rest[:-1], rest[-1]
            spec = P(None, "data") if scan_dim else P("data")
            f = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P(), P()) + tuple(spec for _ in batch) + (P(),),
                out_specs=(P(), P(), P()),
                check_vma=False)
            return f(syn0, syn1, *batch, lr)
        return jax.jit(sharded, donate_argnums=(0, 1))

    return make(step, False), make(epoch, True)


def _sgns_math_table_sharded(rows, axis, syn0_l, syn1_l, centers, contexts,
                             negatives, lr):
    """SGNS step with VOCAB-SHARDED tables: each device owns ``rows``
    consecutive table rows; the index batch is REPLICATED. Row gathers are
    mask-and-psum collectives; scatters apply locally (each device updates
    only its own rows — no table traffic at all).

    This is the >HBM tier of InMemoryLookupTable.java's role: the
    replicated-table _dist_fns path trades compute for exactness when the
    tables fit (syn0+syn1 at V=100k/D=300 is 240 MB — single chip); this
    path shards memory V/n per chip for vocabularies that don't, at the
    cost of replicated dense math + O(B*K*D) psum gathers per step."""
    shard = jax.lax.axis_index(axis)
    lo = shard * rows

    def gather(table_l, idx):
        local = idx - lo
        ok = ((local >= 0) & (local < rows))
        vals = jnp.take(table_l, jnp.clip(local, 0, rows - 1), axis=0)
        vals = vals * ok[..., None].astype(vals.dtype)
        return jax.lax.psum(vals, axis)

    def scatter_mean_local(table_l, idx, grads):
        local = idx - lo
        ok = ((local >= 0) & (local < rows)).astype(grads.dtype)
        safe = jnp.clip(local, 0, rows - 1)
        grads = grads * ok[..., None]
        num = jnp.zeros_like(table_l).at[safe].add(grads)
        cnt = jnp.zeros(rows, grads.dtype).at[safe].add(ok)
        return table_l - lr * num / jnp.maximum(cnt, 1.0)[:, None]

    return _sgns_core(
        lambda idx: gather(syn0_l, idx),
        lambda idx: gather(syn1_l, idx),
        lambda idx, g: scatter_mean_local(syn0_l, idx, g),
        lambda idx, g: scatter_mean_local(syn1_l, idx, g),
        centers, contexts, negatives)


def _dist_fns_table_sharded(mesh, rows):
    """(step, epoch) with tables sharded P('data') by rows and batches
    replicated. Complements _dist_fns (replicated tables, sharded batch)."""
    from jax.sharding import PartitionSpec as P

    math = functools.partial(_sgns_math_table_sharded, rows, "data")

    def step(syn0, syn1, *rest):
        batch, lr = rest[:-1], rest[-1]
        return math(syn0, syn1, *batch, lr)

    epoch = _epoch_body(math)

    def make(fn):
        def sharded(syn0, syn1, *rest):
            batch, lr = rest[:-1], rest[-1]
            f = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P("data"), P("data")) + tuple(
                    P() for _ in batch) + (P(),),
                out_specs=(P("data"), P("data"), P()),
                check_vma=False)
            return f(syn0, syn1, *batch, lr)
        return jax.jit(sharded, donate_argnums=(0, 1))

    return make(step), make(epoch)


class SequenceVectors:
    """Generic embedding trainer over element sequences (reference:
    SequenceVectors.java — Word2Vec, DeepWalk walks, ParagraphVectors all run
    through this)."""

    def __init__(self, *, vector_size=100, window=5, min_count=5, negative=5,
                 learning_rate=0.025, min_learning_rate=1e-4, epochs=1,
                 batch_size=2048, subsample=1e-3, use_hierarchic_softmax=False,
                 algorithm="skipgram", seed=123, mesh=None,
                 shard_tables=False):
        self.mesh = mesh  # jax Mesh with a "data" axis -> distributed fit
        # shard_tables: syn0/syn1 rows shard V/n per device (batches
        # replicate) — for vocabularies whose tables exceed one chip's HBM;
        # SGNS only (see _sgns_math_table_sharded)
        if shard_tables and mesh is None:
            raise ValueError("shard_tables=True requires mesh= (the tables "
                             "shard over the mesh 'data' axis)")
        self.shard_tables = bool(shard_tables)
        if self.shard_tables and (use_hierarchic_softmax
                                  or algorithm != "skipgram"):
            raise ValueError("shard_tables supports skipgram-negative-"
                             "sampling only")
        if mesh is not None and not shard_tables \
                and batch_size % mesh.shape["data"]:
            raise ValueError(
                f"batch_size {batch_size} must divide by the mesh data "
                f"axis size {mesh.shape['data']}")
        self._dist_cache = {}
        self.examples_dropped = 0
        self.vector_size = vector_size
        self.window = window
        self.min_count = min_count
        self.negative = negative
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.epochs = epochs
        self.batch_size = batch_size
        self.subsample = subsample
        self.use_hs = use_hierarchic_softmax
        self.algorithm = algorithm
        self.seed = seed
        self.vocab: VocabCache | None = None
        self.syn0 = None
        self.syn1 = None
        self._rs = np.random.RandomState(seed)

    # ---- vocab + tables ----

    def build_vocab(self, sequences, _flat=None):
        ctor = VocabConstructor(self.min_count, build_huffman=self.use_hs)
        if _flat is not None:
            self.vocab = ctor.build_from_counts(_flat.uniq, _flat.counts)
        else:
            self.vocab = ctor.build(sequences)
        v, d = len(self.vocab), self.vector_size
        rs = np.random.RandomState(self.seed)
        syn0_host = (rs.rand(v, d).astype(np.float32) - 0.5) / d
        rows = v if not self.use_hs else max(v - 1, 1)
        if self.shard_tables:
            # pad rows to the shard count and place row-sharded: V/n rows
            # of each table live on each device
            from jax.sharding import NamedSharding, PartitionSpec as P
            nd = self.mesh.shape["data"]
            vp = -(-v // nd) * nd
            self._rows_per_shard = vp // nd
            pad = vp - v
            sh = NamedSharding(self.mesh, P("data", None))
            self.syn0 = jax.device_put(
                jnp.asarray(np.pad(syn0_host, ((0, pad), (0, 0)))), sh)
            self.syn1 = jax.device_put(
                jnp.zeros((vp, d), jnp.float32), sh)
        else:
            self.syn0 = jnp.asarray(syn0_host)
            self.syn1 = jnp.asarray(np.zeros((rows, d), np.float32))
        counts = self.vocab.counts().astype(np.float64)
        probs = counts ** 0.75
        self._neg_table = (probs / probs.sum()).astype(np.float64)
        self._neg_alias = AliasTable(self._neg_table)
        # device copies for on-device negative drawing (see _draw_negatives)
        self._neg_prob_dev = jnp.asarray(self._neg_alias.prob, jnp.float32)
        self._neg_alias_dev = jnp.asarray(self._neg_alias.alias, jnp.int32)
        self._neg_key = jax.random.PRNGKey(self.seed)
        total = counts.sum()
        freq = counts / total
        self._keep_prob = np.minimum(1.0, np.sqrt(self.subsample / np.maximum(freq, 1e-12))
                                     + self.subsample / np.maximum(freq, 1e-12))
        if self.use_hs:
            self._max_code = max((len(w.codes) for w in self.vocab._by_index), default=1)
            # whole-vocab Huffman path tables: batch lookup = one fancy index
            L = self._max_code
            self._hs_pts = np.zeros((v, L), np.int32)
            self._hs_codes = np.zeros((v, L), np.float32)
            self._hs_mask = np.zeros((v, L), np.float32)
            for r, vw in enumerate(self.vocab._by_index):
                k = len(vw.codes)
                self._hs_pts[r, :k] = vw.points
                self._hs_codes[r, :k] = vw.codes
                self._hs_mask[r, :k] = 1.0
        return self

    # ---- pair generation (host side, fully vectorized) ----
    #
    # The reference feeds its C++ AggregateSkipGram kernel from multiple
    # Hogwild threads (SkipGram.java:271-283). Here the host pipeline is
    # whole-array numpy: the corpus is one flat index array + sequence-id
    # array; pairs for all centers fall out of O(window) shifted comparisons.
    # No Python loop ever touches an individual token.

    def _encode(self, seq):
        idx = [self.vocab.index_of(t) for t in seq]
        return [i for i in idx if i >= 0]

    def _encode_corpus(self, sequences, _flat=None):
        """Flatten to (flat_idx [N], seq_id [N]); computed once per fit.

        Token->index mapping runs through ONE np.unique pass over the whole
        corpus (shared with vocab construction when fit() builds both) + one
        dict lookup PER DISTINCT TOKEN, instead of a Python dict hit per
        token — the encoding half of the reference's multithreaded host
        pipeline (SequenceVectors VectorCalculationsThread tokenize/lookup
        stage). Falls back to per-token dict lookups for token types
        np.unique cannot order."""
        corpus = _flat if _flat is not None else flatten_corpus(sequences)
        if corpus is None:  # exotic token types: dict path
            enc = [self._encode(s) for s in sequences]
            flat = np.asarray([i for e in enc for i in e], np.int32)
            seq_id = np.repeat(np.arange(len(enc), dtype=np.int32),
                               [len(e) for e in enc])
            return flat, seq_id
        lut = np.fromiter((self.vocab.index_of(t) for t in corpus.uniq),
                          np.int32, len(corpus.uniq))
        flat_all = lut[corpus.inverse] if len(corpus.inverse) else \
            np.zeros(0, np.int32)
        seq_id_all = np.repeat(
            np.arange(len(corpus.lens), dtype=np.int32), corpus.lens)
        keep = flat_all >= 0  # drop out-of-vocab tokens
        return flat_all[keep].astype(np.int32), seq_id_all[keep]

    def _subsampled(self, flat, seq_id):
        """Per-epoch frequent-word subsampling (word2vec p_keep)."""
        if self.subsample <= 0 or len(flat) == 0:
            return flat, seq_id
        keep = self._rs.random_sample(len(flat)) < self._keep_prob[flat]
        return flat[keep], seq_id[keep]

    def _pairs_from_corpus(self, flat, seq_id):
        """All (center, context) skip-gram pairs with per-center dynamic
        window b ~ U[1, window], as O(window) shifted array ops."""
        n = len(flat)
        if n < 2:
            z = np.zeros((0,), np.int32)
            return z, z
        b = self._rs.randint(1, self.window + 1, size=n)
        centers, contexts = [], []
        for off in range(1, self.window + 1):
            same = seq_id[:-off] == seq_id[off:]
            # center at pos, context at pos+off (window of the center rules)
            m = same & (b[:-off] >= off)
            centers.append(flat[:-off][m]); contexts.append(flat[off:][m])
            # center at pos+off, context at pos
            m = same & (b[off:] >= off)
            centers.append(flat[off:][m]); contexts.append(flat[:-off][m])
        return (np.concatenate(centers).astype(np.int32),
                np.concatenate(contexts).astype(np.int32))

    def _pairs_from_sequences(self, sequences):
        flat, seq_id = self._encode_corpus(sequences)
        return self._pairs_from_corpus(*self._subsampled(flat, seq_id))

    # rows per device draw call; fixed so the draw compiles once (the
    # per-epoch pair count varies with subsampling)
    _NEG_CHUNK = 1 << 17

    def _draw_negatives(self, shape):
        """Negative samples drawn ON DEVICE in fixed-shape jitted chunks.

        Host alias draws plus the [N,K] host->device transfer (27 MB/epoch
        at the bench config) both disappear when the draw happens
        device-side.
        The result stays on device; _run_batched slices it like any other
        batch array."""
        n, k = shape
        if n == 0:
            return jnp.zeros((0, k), jnp.int32)
        chunks = []
        for _ in range(-(-n // self._NEG_CHUNK)):
            self._neg_key, sub = jax.random.split(self._neg_key)
            chunks.append(_alias_draw_chunk(
                self._neg_prob_dev, self._neg_alias_dev, sub,
                (self._NEG_CHUNK, k)))
        negs = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
        return negs[:n]

    def _cbow_windows_from_corpus(self, flat, seq_id):
        """Padded CBOW windows as one gather: positions [N,1] + offsets
        [1,2W], masked where out-of-sequence or beyond the dynamic window."""
        W = 2 * self.window
        n = len(flat)
        if n == 0:
            z = np.zeros((0, W), np.int32)
            return z, np.zeros((0, W), np.float32), np.zeros((0,), np.int32)
        b = self._rs.randint(1, self.window + 1, size=n)
        offs = np.concatenate([np.arange(-self.window, 0),
                               np.arange(1, self.window + 1)])  # [2W]
        pos = np.arange(n)[:, None]                              # [N,1]
        j = pos + offs[None, :]                                  # [N,2W]
        jc = np.clip(j, 0, n - 1)
        valid = ((j >= 0) & (j < n)
                 & (seq_id[jc] == seq_id[:, None])
                 & (np.abs(offs)[None, :] <= b[:, None]))
        has_ctx = valid.any(axis=1)
        ctx = np.where(valid, flat[jc], 0).astype(np.int32)[has_ctx]
        mask = valid.astype(np.float32)[has_ctx]
        return ctx, mask, flat[has_ctx]

    def _cbow_windows(self, sequences):
        flat, seq_id = self._encode_corpus(sequences)
        return self._cbow_windows_from_corpus(*self._subsampled(flat, seq_id))

    # ---- training ----

    def fit(self, sequences):
        """sequences: iterable (re-iterable) of token lists.

        Host/device overlap comes free from jax's async dispatch: losses stay
        on device until the epoch ends (a per-step ``float(loss)`` would
        force a sync and serialize host batch prep against device steps —
        the reference gets the same overlap from its prefetch threads).
        """
        seq_list = [list(s) for s in sequences]
        self.examples_dropped = 0
        flat = flatten_corpus(seq_list)  # ONE pass feeds vocab + encoding
        if self.vocab is None:
            self.build_vocab(seq_list, _flat=flat)
        corpus = self._encode_corpus(seq_list, _flat=flat)  # once, not per epoch
        total_steps = max(self.epochs, 1)
        losses = []
        for epoch in range(self.epochs):
            frac = epoch / total_steps
            lr = max(self.learning_rate * (1 - frac), self.min_learning_rate)
            if self.algorithm == "cbow" and not self.use_hs:
                ctx, cmask, targets = self._cbow_windows_from_corpus(
                    *self._subsampled(*corpus))
                perm = self._rs.permutation(len(targets))
                ctx, cmask, targets = ctx[perm], cmask[perm], targets[perm]
                negs = self._draw_negatives((len(targets), self.negative))
                losses += self._run_batched(
                    _cbow_epoch, _cbow_step, (ctx, cmask, targets, negs),
                    lr, math_fn=_cbow_math)
                continue
            centers, contexts = self._pairs_from_corpus(
                *self._subsampled(*corpus))
            perm = self._rs.permutation(len(centers))
            centers, contexts = centers[perm], contexts[perm]
            if self.use_hs:
                pts, codes, mask = self._huffman_batch(contexts)
                losses += self._run_batched(
                    _hs_epoch, _hs_step, (centers, pts, codes, mask),
                    lr, math_fn=_hs_math)
            else:
                negs = self._draw_negatives((len(centers), self.negative))
                losses += self._run_batched(
                    _sgns_epoch, _sgns_step, (centers, contexts, negs),
                    lr, math_fn=_sgns_math)
        self.loss_history = fetch_losses(losses)
        return self

    # batches per scanned jit call; fixed so the scan compiles ONCE and is
    # reused across epochs/corpora (a whole-epoch scan would bake the corpus
    # size into the compiled shape)
    SCAN_CHUNK = 32

    def _run_batched(self, epoch_fn, step_fn, arrays, lr, math_fn=None):
        """Split aligned arrays into SCAN_CHUNK-sized groups of [B, ...] full
        batches, each group executed as ONE scanned jit call; leftover full
        batches and the ragged tail go through the per-step jit. Returns the
        list of (device) per-batch losses.

        With a mesh, batches shard over the ``data`` axis (psum-pooled
        scatter stats — see _dist_fns); ragged tails truncate to a multiple
        of the axis size (at most n_devices-1 pairs dropped per epoch,
        recorded in ``examples_dropped``)."""
        if self.mesh is not None and self.shard_tables:
            if "table_sharded" not in self._dist_cache:
                self._dist_cache["table_sharded"] = _dist_fns_table_sharded(
                    self.mesh, self._rows_per_shard)
            step_fn, epoch_fn = self._dist_cache["table_sharded"]
        elif self.mesh is not None:
            if math_fn not in self._dist_cache:
                self._dist_cache[math_fn] = _dist_fns(math_fn, self.mesh)
            step_fn, epoch_fn = self._dist_cache[math_fn]
            nd = self.mesh.shape["data"]
            n_keep = (len(arrays[0]) // nd) * nd
            self.examples_dropped += len(arrays[0]) - n_keep
            arrays = tuple(a[:n_keep] for a in arrays)
        n = len(arrays[0])
        bs = self.batch_size
        ck = self.SCAN_CHUNK
        losses = []
        i = 0
        while n - i >= ck * bs:
            batches = tuple(jnp.asarray(
                a[i:i + ck * bs].reshape(ck, bs, *a.shape[1:]))
                for a in arrays)
            self.syn0, self.syn1, ls = epoch_fn(self.syn0, self.syn1,
                                                batches, lr)
            losses += list(ls)
            i += ck * bs
        while i < n:
            tail = tuple(jnp.asarray(a[i:i + bs]) for a in arrays)
            self.syn0, self.syn1, loss = step_fn(self.syn0, self.syn1,
                                                 *tail, lr)
            losses.append(loss)
            i += bs
        return losses

    def _huffman_batch(self, targets):
        """Padded Huffman paths for a batch — one fancy index into the
        precomputed whole-vocab tables (built in build_vocab)."""
        return (self._hs_pts[targets], self._hs_codes[targets],
                self._hs_mask[targets])

    # ---- query API (reference: WordVectors interface) ----

    def get_word_vector(self, word):
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def has_word(self, word):
        return self.vocab is not None and word in self.vocab

    def similarity(self, w1, w2):
        a, b = self.get_word_vector(w1), self.get_word_vector(w2)
        if a is None or b is None:
            return float("nan")
        return float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))

    def words_nearest(self, word, top_n=10):
        i = self.vocab.index_of(word)
        if i < 0:
            return []
        m = np.asarray(self.syn0)
        norms = m / (np.linalg.norm(m, axis=1, keepdims=True) + 1e-12)
        sims = norms @ norms[i]
        order = np.argsort(-sims)
        return [(self.vocab.word_for(j), float(sims[j]))
                for j in order if j != i][:top_n]


class Word2Vec(SequenceVectors):
    """(reference: models/word2vec/Word2Vec.java — SequenceVectors over
    tokenized sentences)."""

    def __init__(self, *, tokenizer_factory=None, **kwargs):
        super().__init__(**kwargs)
        from deeplearning4j_tpu.text.tokenization import \
            default_tokenizer_factory
        self.tokenizer_factory = tokenizer_factory or \
            default_tokenizer_factory()

    def fit_sentences(self, sentences):
        seqs = [self.tokenizer_factory.create(s).get_tokens() for s in sentences]
        return self.fit(seqs)

    def fit_iterator(self, sentence_iterator):
        """Train from any corpus SentenceIterator (reference:
        Word2Vec.Builder.iterate(SentenceIterator) — the front door of
        text/corpus.py). The iterator is fully consumed once; multi-epoch
        replay happens device-side over the materialized sequences."""
        return self.fit_sentences(list(sentence_iterator))
