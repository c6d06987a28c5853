"""Pure-jnp oracles for every Pallas kernel (the `ref.py` layer).

These are the semantics contracts: tests sweep shapes/dtypes and
``assert_allclose`` each kernel against the function here.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@jax.jit
def scaled_leaves(lr, leaf: jax.Array) -> jax.Array:
    """``lr * leaf``, rounded once, as an XLA program of its own.

    Every tree-sum here (and the heap walk in `core.tree`) adds leaf values
    that were multiplied by the learning rate *before* the gather, and runs
    this multiply outside the accumulating program: inside one program XLA
    may fuse the multiply into the add and contract the pair into an FMA,
    in one program but not in another, which costs bit parity between
    oracles and kernels by an ulp.  The kernels scale their VMEM leaf block
    the same way before their exact one-hot gathers.
    """
    return jnp.asarray(lr, jnp.float32) * leaf.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def histogram_ref(codes: jax.Array, node_pos: jax.Array, stats: jax.Array,
                  *, n_nodes: int, n_bins: int) -> jax.Array:
    """(n, m) codes, (n,) nodes, (n, c) stats -> (n_nodes, m, n_bins, c)."""
    seg_base = node_pos.astype(jnp.int32) * n_bins

    def per_feature(col):
        seg = seg_base + col.astype(jnp.int32)
        return jax.ops.segment_sum(stats, seg, num_segments=n_nodes * n_bins)

    hist = jax.vmap(per_feature, in_axes=1)(codes)        # (m, nodes*B, c)
    m = codes.shape[1]
    return hist.reshape(m, n_nodes, n_bins, -1).transpose(1, 0, 2, 3)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins",
                                             "n_channels", "n_parts",
                                             "lane_pad", "row_tile"))
def histogram_tiles_ref(tile_node: jax.Array, codes_t: jax.Array,
                        parts: jax.Array, *, n_nodes: int, n_bins: int,
                        n_channels: int, n_parts: int, lane_pad: int,
                        row_tile: int = 256) -> jax.Array:
    """Oracle for the partitioned tiles kernel (`hist_kernel.hist_tiles_pallas`).

    Same contract: (n_tiles,) sorted tile->node map, (m, S) partition-ordered
    codes + (S, P) bf16 stats parts (``n_parts`` lane groups of
    ``n_channels``, `hist_kernel.stat_parts`) -> (m, n_nodes, n_bins, C)
    node histograms, C = ``n_channels`` padded to ``lane_pad``.  Per tile:
    one bf16 one-hot contraction accumulated in float32; each node's tiles
    summed in tile order (its first tile taken as it is, each later one
    added); then each channel's part sums added in group order.  The
    kernel runs the identical body (exact 0/1 selections, one fixed op
    order), so it is bit-identical to this oracle.
    """
    m, s = codes_t.shape
    c = n_channels
    lanes = -(-c // lane_pad) * lane_pad
    n_tiles = s // row_tile
    codes_r = codes_t.reshape(m, n_tiles, row_tile).astype(jnp.int32)
    parts_r = parts.reshape(n_tiles, row_tile, -1)
    onehot = (codes_r[..., None]
              == jnp.arange(n_bins, dtype=jnp.int32)).astype(jnp.bfloat16)

    def per_tile(oh_t, pt_t):                              # (m, TN, B), (TN, P)
        return jax.vmap(lambda oh: jax.lax.dot_general(
            oh, pt_t, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))(oh_t)     # (m, B, P)

    tiles = jax.vmap(per_tile, in_axes=(1, 0))(onehot, parts_r)  # (T, m, B, P)
    tile_node = tile_node.astype(jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             tile_node[1:] != tile_node[:-1]])

    def fold(acc, xs):                                     # acc (nodes, m, B, P)
        node, is_first, tile = xs
        new = jnp.where(is_first, tile, acc[node] + tile)
        return acc.at[node].set(new), None

    acc = jnp.zeros((n_nodes, m, n_bins, parts.shape[1]), jnp.float32)
    acc, _ = jax.lax.scan(fold, acc, (tile_node, first, tiles))
    hist = acc[..., :c]
    for k in range(1, n_parts):
        hist = hist + acc[..., k * c:(k + 1) * c]
    hist = jnp.pad(hist, ((0, 0),) * 3 + ((0, lanes - c),))
    return hist.transpose(1, 0, 2, 3)                      # (m, nodes, B, C)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def split_scan_ref(hist: jax.Array, lam: jax.Array, min_data: jax.Array,
                   mask: jax.Array, *, n_nodes: int, n_bins: int):
    """Oracle for the split-scan kernel, in its native histogram layout.

    Args:
      hist: (m, n_nodes * n_bins, c) — channels [0:c-1] gradient sums, [c-1]
            counts (NO lane padding here; the wrapper strips it first).
      mask: (m,) float32; 0 disables a feature.
    Returns:
      (best_gain, best_idx): each (n_nodes,); idx = feature * n_bins + bin,
      gain = -inf when the node has no legal split.
    """
    m = hist.shape[0]
    h = hist.reshape(m, n_nodes, n_bins, -1).transpose(1, 0, 2, 3)
    csum = jnp.cumsum(h, axis=2)                           # (nodes, m, B, c)
    total = csum[:, :, -1:, :]
    gl, cl = csum[..., :-1], csum[..., -1]
    gr = total[..., :-1] - gl
    cr = total[..., -1] - cl
    s_left = jnp.sum(jnp.square(gl), axis=-1) / (cl + lam)
    s_right = jnp.sum(jnp.square(gr), axis=-1) / (cr + lam)
    s_parent = (jnp.sum(jnp.square(total[..., :-1]), axis=-1)
                / (total[..., -1] + lam))
    gain = 0.5 * (s_left + s_right - s_parent)             # (nodes, m, B)
    legal = (jnp.arange(n_bins) < n_bins - 1)[None, None, :]
    legal = legal & (cl >= min_data) & (cr >= min_data)
    legal = legal & (mask[None, :, None] > 0.0)
    gain = jnp.where(legal, gain, -jnp.inf)
    flat = gain.reshape(n_nodes, m * n_bins)
    idx = jnp.argmax(flat, axis=1).astype(jnp.int32)
    best = jnp.take_along_axis(flat, idx[:, None], axis=1)[:, 0]
    return best, idx


@functools.partial(jax.jit, static_argnames=("depth",))
def node_walk_ref(feat: jax.Array, thr: jax.Array, left: jax.Array,
                  right: jax.Array, codes: jax.Array, *, depth: int
                  ) -> jax.Array:
    """Pointer-chasing walk of ONE sparse-topology tree: terminal node ids.

    ``left``/``right`` are explicit child pointers over a unified node id
    space; terminal nodes self-loop (``left[i] == right[i] == i``), so the
    walk is depth-synchronous with a fixed ``depth`` iteration bound — extra
    iterations past a terminal node are exact no-ops.  This is the per-tree
    oracle every packed-forest consumer (predict kernel, SHAP paths, apply
    embeddings) is tested against.
    """
    n = codes.shape[0]
    pos = jnp.zeros((n,), jnp.int32)
    for _ in range(depth):
        fi = feat[pos]
        code = codes[jnp.arange(n), fi].astype(jnp.int32)
        bit = code > thr[pos]
        pos = jnp.where(bit, right[pos], left[pos]).astype(jnp.int32)
    return pos


def forest_apply_ref(F_init: jax.Array, codes: jax.Array, feat: jax.Array,
                     thr: jax.Array, left: jax.Array, right: jax.Array,
                     leaf: jax.Array, out_col: jax.Array,
                     lr: jax.Array, *, depth: int) -> jax.Array:
    """Oracle for the packed-forest traversal kernel (pointer-chasing walk).

    Args:
      F_init:  (n, d) float32 initial scores (donated; accumulated per tree).
      codes:   (n, m) binned features.
      feat, thr: (T, N) int32 per-node split features / thresholds in the
                 unified node id space (go left when ``code <= thr``; unused
                 on terminal nodes).
      left, right: (T, N) int32 explicit child pointers; terminal nodes
                 self-loop (``left[i] == right[i] == i``), so trees of
                 arbitrary topology (level-wise heaps, leaf-wise best-first
                 trees) walk under one fixed ``depth`` bound.
      leaf:    (T, N, w) float32 node-indexed leaf blocks (zero on internal
               nodes).
      out_col: (T,) int32 starting output column of each tree's leaf block
               (0 for full-width trees, the output index for one-vs-all).
    Returns:
      (n, d) float32 ``F_init + sum_t (lr * leaf_t)[walk_t(codes)]``,
      accumulated tree-by-tree in scan order — bit-identical to
      `tree.predict_forest` for heap-canonicalized full-width trees and to
      the Pallas kernel's grid order.
    """
    return _tree_sum_ref(F_init, codes, feat, thr, left, right,
                         scaled_leaves(lr, leaf), out_col, depth=depth)


@functools.partial(jax.jit, static_argnames=("depth",), donate_argnums=(0,))
def _tree_sum_ref(F_init, codes, feat, thr, left, right, leaf_s, out_col, *,
                  depth: int):
    n = codes.shape[0]
    w = leaf_s.shape[2]

    def body(acc, tree_arrays):
        f, th, lft, rgt, v, col = tree_arrays
        pos = node_walk_ref(f, th.astype(jnp.int32), lft, rgt, codes,
                            depth=depth)
        contrib = v[pos]                                   # (n, w)
        if w == acc.shape[1]:          # full-width leaf block: col is 0
            acc = acc + contrib
        else:                          # narrow block at a traced column
            cur = jax.lax.dynamic_slice(acc, (0, col), (n, w))
            acc = jax.lax.dynamic_update_slice(acc, cur + contrib, (0, col))
        return acc, None

    acc, _ = jax.lax.scan(body, F_init.astype(jnp.float32),
                          (feat, thr, left, right, leaf_s,
                           out_col.astype(jnp.int32)))
    return acc


def forest_apply_quant_ref(F_init: jax.Array, codes: jax.Array,
                           feat: jax.Array, thr: jax.Array, left: jax.Array,
                           right: jax.Array, leaf: jax.Array,
                           leaf_scale: jax.Array, out_col: jax.Array,
                           lr: jax.Array, *, depth: int) -> jax.Array:
    """Oracle for the QUANTIZED packed-forest traversal.

    Same contract as `forest_apply_ref` with quantized storage: ``thr`` may
    be uint8 (bin codes — widened to int32 for the walk, so split decisions
    are bit-identical to the fp32 forest), ``leaf`` is int8 or bfloat16 with
    a per-tree fp32 ``leaf_scale`` (T, 1); the dequantized value is
    ``leaf.astype(f32) * scale`` and accumulation stays fp32.  The block is
    dequantized before the terminal gather (an exact selection), so this
    oracle is bit-identical to `forest_apply_ref` on
    `core.quantize.dequantize_forest` of the same model — the exactness
    contract the serving-tier tests assert.
    """
    deq = leaf.astype(jnp.float32) * leaf_scale.astype(jnp.float32)[:, :, None]
    return forest_apply_ref(F_init, codes, feat, thr, left, right, deq,
                            out_col, lr, depth=depth)


# ---------------------------------------------------------------------------
# TreeSHAP over packed root-to-leaf paths (oracle for kernels/shap_kernel.py).
# ---------------------------------------------------------------------------

# "No upper bin bound" sentinel for merged path conditions (``o = lo < code
# <= hi``).  Lives here — the semantics-contract module both the path
# extractor (`explain.paths`) and the kernel wrapper (`ops.tree_shap`)
# import — so padding fills can never drift from real slot values.  Codes
# are < 2^20 always and the value is exactly representable in float32, so
# the kernel's f32 comparisons match the oracle's int comparisons.
SHAP_BIG_BIN = 2 ** 20


def _unwind_weights(depth: int) -> list:
    """Shapley permutation weights ``W(k, D) = k!(D-1-k)!/D!``, k=0..D-1."""
    f = math.factorial
    return [f(k) * f(depth - 1 - k) / f(depth) for k in range(depth)]


def _poly_extend(coeffs: list, z_s, o_s) -> list:
    """Multiply a coefficient list by ``(z_s + o_s * x)`` (Lundberg EXTEND)."""
    out = [coeffs[0] * z_s]
    for k in range(1, len(coeffs)):
        out.append(coeffs[k] * z_s + coeffs[k - 1] * o_s)
    out.append(coeffs[-1] * o_s)
    return out


def path_unwind_psis(o_slots: list, z_slots: list) -> list:
    """Per-slot UNWIND sums Ψ_s for the leaf-path Shapley formula.

    For a root-to-leaf path with ``D`` unique feature slots, slot ``s``
    carrying one-fraction ``o_s`` (did the explained row follow this slot's
    splits) and zero-fraction ``z_s`` (expected flow-through when the feature
    is unknown), the Shapley contribution of slot ``s`` from this path is
    ``v_leaf * (o_s - z_s) * Ψ_s`` with

        Ψ_s = Σ_k W(k, D) * [x^k] Π_{j != s} (z_j + o_j x),

    the subset-sum of Lundberg et al. (2018) written as a polynomial
    convolution.  Implemented division-free: EXTEND builds prefix/suffix
    products of the path polynomial, UNWIND of slot ``s`` is the prefix[s] ×
    suffix[s+1] convolution — numerically safe when ``z = 0`` (empty
    subtrees) and with one fixed op order, so the Pallas kernel that shares
    this helper is bit-identical to the oracle.  Inputs are length-``D``
    lists of broadcast-compatible arrays (slot axis unstacked so the caller
    controls layout); output is the matching list of Ψ arrays.

    Padding slots with ``o = z = 1`` is exactly invariant (a null player:
    dividing the path polynomial by ``(1 + x)`` and reweighting with
    ``W(k, D-1)`` yields the same Ψ), which is what lets every path use a
    fixed slot count ``D`` regardless of how many unique features it has.
    """
    depth = len(o_slots)
    ones = jnp.ones_like(o_slots[0])
    prefixes = [[ones]]
    for s in range(depth):
        prefixes.append(_poly_extend(prefixes[-1], z_slots[s], o_slots[s]))
    suffixes = [None] * (depth + 1)
    suffixes[depth] = [ones]
    for s in range(depth - 1, -1, -1):
        suffixes[s] = _poly_extend(suffixes[s + 1], z_slots[s], o_slots[s])
    W = _unwind_weights(depth)
    psis = []
    for s in range(depth):
        pre, suf = prefixes[s], suffixes[s + 1]
        psi = None
        for k in range(depth):                 # degree-k coeff of pre ⊛ suf
            ck = None
            for j in range(max(0, k - len(suf) + 1),
                           min(k, len(pre) - 1) + 1):
                term = pre[j] * suf[k - j]
                ck = term if ck is None else ck + term
            if ck is None:
                continue
            wck = ck * jnp.float32(W[k])
            psi = wck if psi is None else psi + wck
        psis.append(psi)
    return psis


def _path_contribs(codes_i: jax.Array, sf, lo, hi, z) -> jax.Array:
    """Per-(row, leaf, slot) weighted Shapley factors ``(o - z) * Ψ``.

    codes_i: (n, m) int32; sf/lo/hi: (L, D) int32 slot conditions
    (one-fraction ``o = lo < code <= hi``; padding slots use ``sf = -1``,
    ``lo = -1`` so ``o = 1`` always); z: (L, D) float32 zero-fractions.
    """
    depth = sf.shape[1]
    c = codes_i[:, jnp.maximum(sf, 0)]                     # (n, L, D)
    o = ((c > lo) & (c <= hi)).astype(jnp.float32)
    o_slots = [o[..., s] for s in range(depth)]
    z_slots = [z[..., s] for s in range(depth)]
    psis = path_unwind_psis(o_slots, z_slots)
    return jnp.stack([(o_slots[s] - z_slots[s]) * psis[s]
                      for s in range(depth)], axis=-1)     # (n, L, D)


def _scatter_contribs(acc, contrib, sf, leaf_s, col):
    """Fold per-slot contributions into the (n, m, d) attribution tensor.

    Slot -> feature is an exact one-hot selection (unique features per path,
    so at most one non-zero per (leaf, feature)); leaf -> output reduction is
    a single (n*m, L) x (L, w) contraction — the same contraction shapes the
    Pallas kernel uses, keeping the two bit-identical within the aligned
    depth-3 shape envelope (beyond it, XLA's per-program FMA/fusion choices
    cap cross-program agreement at float32 add-order noise; the parity
    tests document both regimes).
    """
    n, m_feats, d = acc.shape
    L, w = leaf_s.shape
    f1h = (sf[..., None] == jnp.arange(m_feats, dtype=jnp.int32)
           ).astype(jnp.float32)                           # (L, D, m)
    # HIGHEST, as in the kernel: a TPU's default f32 matmul is one bf16 pass.
    A = jnp.einsum("nls,lsf->nlf", contrib, f1h,
                   precision=jax.lax.Precision.HIGHEST)    # exact selection
    At = A.transpose(0, 2, 1).reshape(n * m_feats, L)
    # The kernel contracts a lane-padded leaf block (>= 8 columns): pad a
    # narrow block the same way, since a matrix-vector product may sum the
    # leaf axis in another order than a matrix-matrix one.
    res = jax.lax.dot_general(At, jnp.pad(leaf_s, ((0, 0), (0, (-w) % 8))),
                              dimension_numbers=(((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    res = res[:, :w].reshape(n, m_feats, w)
    if w == d:                                 # full-width leaf block: col 0
        return acc + res
    cur = jax.lax.dynamic_slice(acc, (0, 0, col), (n, m_feats, w))
    return jax.lax.dynamic_update_slice(acc, cur + res, (0, 0, col))


def tree_shap_ref(phi_init: jax.Array, codes: jax.Array, slot_feat: jax.Array,
                  slot_lo: jax.Array, slot_hi: jax.Array, slot_z: jax.Array,
                  leaf: jax.Array, out_col: jax.Array, lr: jax.Array, *,
                  depth: int) -> jax.Array:
    """Oracle for the Pallas path-walk SHAP kernel (path-dependent TreeSHAP).

    Args:
      phi_init: (n, m, d) float32 initial attributions (donated; usually 0).
      codes:    (n, m) binned features.
      slot_feat, slot_lo, slot_hi: (T, L, D) int32 per-(tree, leaf, slot)
                merged path conditions (`explain.paths.build_path_pack`);
                padding slots carry ``feat = -1`` / ``o = 1``.
      slot_z:   (T, L, D) float32 zero-fractions (cover ratios).
      leaf:     (T, L, w) float32 leaf blocks; out_col: (T,) int32 column of
                each tree's block (as in `forest_apply_ref`).
    Returns:
      (n, m, d) float32 ``phi_init + lr * sum_t shap_t(codes)``, accumulated
      tree-by-tree in scan order (the Pallas grid order).  Local accuracy:
      summing over the feature axis and adding the expected value gives the
      raw ensemble prediction exactly (per tree, per path).
    """
    return _tree_shap_sum_ref(phi_init, codes, slot_feat, slot_lo, slot_hi,
                              slot_z, scaled_leaves(lr, leaf), out_col,
                              depth=depth)


@functools.partial(jax.jit, static_argnames=("depth",), donate_argnums=(0,))
def _tree_shap_sum_ref(phi_init, codes, slot_feat, slot_lo, slot_hi, slot_z,
                       leaf_s, out_col, *, depth: int):
    codes_i = codes.astype(jnp.int32)

    def body(acc, xs):
        sf, lo, hi, z, v, col = xs
        contrib = _path_contribs(codes_i, sf, lo, hi, z.astype(jnp.float32))
        return _scatter_contribs(acc, contrib, sf, v, col), None

    acc, _ = jax.lax.scan(body, phi_init.astype(jnp.float32),
                          (slot_feat, slot_lo, slot_hi, slot_z, leaf_s,
                           out_col.astype(jnp.int32)))
    return acc


def tree_shap_interventional_ref(phi_init: jax.Array, codes: jax.Array,
                                 bg_codes: jax.Array, slot_feat: jax.Array,
                                 slot_lo: jax.Array, slot_hi: jax.Array,
                                 leaf: jax.Array, out_col: jax.Array,
                                 lr: jax.Array, *, depth: int) -> jax.Array:
    """Interventional TreeSHAP against a background dataset.

    Identical path machinery to `tree_shap_ref`, but the zero-fraction of a
    slot is the *background row's* one-fraction (features take the
    background's values when "absent") and attributions are averaged over
    the ``(B, m)`` background rows — so ``sum(phi) = f(x) - mean_b f(b)``
    exactly and the matching base value is the mean background prediction.
    """
    return _tree_shap_interventional_sum_ref(
        phi_init, codes, bg_codes, slot_feat, slot_lo, slot_hi,
        scaled_leaves(lr, leaf), out_col, depth=depth)


@functools.partial(jax.jit, static_argnames=("depth",), donate_argnums=(0,))
def _tree_shap_interventional_sum_ref(phi_init, codes, bg_codes, slot_feat,
                                      slot_lo, slot_hi, leaf_s, out_col, *,
                                      depth: int):
    codes_i = codes.astype(jnp.int32)
    bg_i = bg_codes.astype(jnp.int32)
    n_bg = bg_codes.shape[0]

    def body(acc, xs):
        sf, lo, hi, v, col = xs
        c = codes_i[:, jnp.maximum(sf, 0)]                 # (n, L, D)
        o = ((c > lo) & (c <= hi)).astype(jnp.float32)
        cb = bg_i[:, jnp.maximum(sf, 0)]                   # (B, L, D)
        ob = ((cb > lo) & (cb <= hi)).astype(jnp.float32)
        o_slots = [o[..., s] for s in range(depth)]

        def bg_body(acc_c, zb):                            # zb: (L, D)
            z_slots = [zb[..., s] for s in range(depth)]
            psis = path_unwind_psis(o_slots, z_slots)
            contrib = jnp.stack([(o_slots[s] - z_slots[s]) * psis[s]
                                 for s in range(depth)], axis=-1)
            return acc_c + contrib, None

        csum, _ = jax.lax.scan(bg_body, jnp.zeros(o.shape, jnp.float32), ob)
        contrib = csum / jnp.float32(n_bg)
        return _scatter_contribs(acc, contrib, sf, v, col), None

    acc, _ = jax.lax.scan(body, phi_init.astype(jnp.float32),
                          (slot_feat, slot_lo, slot_hi, leaf_s,
                           out_col.astype(jnp.int32)))
    return acc


def _attn_mask(sq: int, sk: int, *, causal: bool, window: int | None,
               q_offset: int) -> jax.Array:
    """(sq, sk) boolean attention mask. q position i attends kv position j iff
    j <= i+q_offset (causal) and i+q_offset - j < window (sliding window)."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    return mask


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset"))
def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
            window: int | None = None, q_offset: int = 0) -> jax.Array:
    """GQA reference attention.

    q: (b, hq, sq, dh); k, v: (b, hkv, sk, dh) with hq % hkv == 0.
    Returns (b, hq, sq, dh) in q.dtype; softmax in float32.
    """
    b, hq, sq, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, dh).astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(dh))
    mask = _attn_mask(sq, k.shape[2], causal=causal, window=window,
                      q_offset=q_offset)
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, sq, dh).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window",))
def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array, *, window: int | None = None
                         ) -> jax.Array:
    """Single-token GQA decode attention against a (possibly padded) KV cache.

    q: (b, hq, dh); k, v: (b, hkv, s, dh); lengths: (b,) valid cache lengths.
    Position of the new token is lengths[b] - 1 after appending.
    """
    b, hq, dh = q.shape
    s = k.shape[2]
    kpos = jnp.arange(s)[None, :]                          # (1, s)
    valid = kpos < lengths[:, None]
    if window is not None:
        valid &= (lengths[:, None] - 1 - kpos) < window
    hkv = k.shape[1]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, dh).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(dh))
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, dh).astype(q.dtype)
