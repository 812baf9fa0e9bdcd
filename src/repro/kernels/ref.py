"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth; kernels are validated against
these in tests/test_kernels.py across shape/dtype sweeps (interpret=True on
CPU).  They are also the implementations the models use on non-TPU backends
(the multi-pod dry-run lowers these; XLA fuses them well).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Attention (GQA, causal / full), the LM hot spot
# ---------------------------------------------------------------------------
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              scale: float | None = None, bias: jax.Array | None = None) -> jax.Array:
    """Grouped-query attention oracle.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D] with Hq % Hkv == 0.
    Softmax in f32 regardless of input dtype; returns q.dtype.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, Sq, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        q_pos = jnp.arange(Sq)[:, None] + (Sk - Sq)  # right-aligned queries
        k_pos = jnp.arange(Sk)[None, :]
        mask = q_pos >= k_pos
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vf)
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, scale: float | None = None,
                      chunk: int = 512, unroll: bool = False) -> jax.Array:
    """Query-chunked attention: exact, never materializes the full S^2.

    The dry-run/CPU production path (flash_attention's role off-TPU): a
    lax.scan over query blocks keeps the live score slice at
    [B, H, chunk, Sk] — the XLA analogue of the Pallas kernel's VMEM tiling.
    Semantics identical to `attention`.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    chunk = min(chunk, Sq)
    if Sq % chunk:
        return attention(q, k, v, causal=causal, scale=scale)
    nq = Sq // chunk
    offset = Sk - Sq

    qf = q.astype(jnp.float32).reshape(B, Hkv, group, nq, chunk, D)
    qf = jnp.moveaxis(qf, 3, 0)  # [nq, B, Hkv, g, chunk, D]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = jnp.arange(Sk)[None, :]

    def body(_, inputs):
        i, qb = inputs
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kf) * scale
        if causal:
            q_pos = i * chunk + jnp.arange(chunk)[:, None] + offset
            logits = jnp.where((q_pos >= k_pos)[None, None, None],
                               logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vf)
        return None, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qf),
                           unroll=True if unroll else 1)
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Hq, Sq, D)
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array | int) -> jax.Array:
    """Single-token decode oracle: q [B, Hq, 1, D], caches [B, Hkv, S, D].

    Positions >= cache_len are masked (cache tail may be uninitialized).
    The caches are consumed in their stored dtype with f32 accumulation
    (`preferred_element_type`) — an explicit astype would materialize an
    f32 copy of the entire cache (2x cache HBM, measured 20+ GiB on the
    gemma decode_32k cell).
    """
    B, Hq, _, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    qf = q.reshape(B, Hkv, group, D).astype(k_cache.dtype)
    logits = jnp.einsum("bhgd,bhkd->bhgk", qf, k_cache,
                        preferred_element_type=jnp.float32) * (D ** -0.5)
    mask = jnp.arange(S)[None, None, None, :] < cache_len
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Weighted temporal composite (paper §V.C: cloud-free global base layer)
# ---------------------------------------------------------------------------
def composite(images: jax.Array, weights: jax.Array,
              eps: float = 1e-6) -> jax.Array:
    """Weighted temporal average over an image stack.

    images: [T, H, W, C] float; weights: [T, H, W] (>= 0; cloud-free and
    verdant pixels get higher weight).  Output: [H, W, C] =
    sum_t w[t]*x[t] / (sum_t w[t] + eps).  All accumulation in f32.
    """
    imf = images.astype(jnp.float32)
    wf = weights.astype(jnp.float32)[..., None]
    num = jnp.sum(imf * wf, axis=0)
    den = jnp.sum(wf, axis=0)
    return (num / (den + eps)).astype(images.dtype)


def composite_weights(cloud_score: jax.Array, nir: jax.Array, red: jax.Array,
                      eps: float = 1e-6) -> jax.Array:
    """The paper's weighting: favor cloud-free, verdant pixels.

    cloud_score: [T, H, W] in [0, 1] (1 = certainly cloud);
    nir/red: [T, H, W] reflectances -> NDVI verdancy term.
    """
    ndvi = (nir - red) / (nir + red + eps)
    verdancy = jnp.clip(ndvi, 0.0, 1.0)
    return (1.0 - cloud_score) * (0.25 + 0.75 * verdancy)


# ---------------------------------------------------------------------------
# Temporal-mean gradient magnitude (paper §V.B: field segmentation edges)
# ---------------------------------------------------------------------------
def grad_mag(images: jax.Array, valid: jax.Array,
             eps: float = 1e-6) -> tuple[jax.Array, jax.Array]:
    """Accumulated cloud-masked spatial gradient magnitude.

    images: [T, H, W, C]; valid: [T, H, W] bool (False = cloud/missing).
    "We then compute the spatial gradient magnitude, ensuring that only
    changes across valid pixels produce nonzero gradients ... accumulated
    over the bands of each image and over the images available."

    Returns (grad_sum [H, W], count [H, W]): per-pixel accumulated gradient
    magnitude and valid-observation count; the temporal-mean gradient image
    is grad_sum / max(count, 1).
    """
    imf = images.astype(jnp.float32)
    vf = valid.astype(jnp.float32)
    # forward differences; a difference is valid only if BOTH pixels are valid
    dx = imf[:, :, 1:, :] - imf[:, :, :-1, :]
    dy = imf[:, 1:, :, :] - imf[:, :-1, :, :]
    vx = vf[:, :, 1:] * vf[:, :, :-1]
    vy = vf[:, 1:, :] * vf[:, :-1, :]
    dx = jnp.pad(dx * vx[..., None], ((0, 0), (0, 0), (0, 1), (0, 0)))
    dy = jnp.pad(dy * vy[..., None], ((0, 0), (0, 1), (0, 0), (0, 0)))
    mag = jnp.sqrt(jnp.sum(dx * dx, axis=-1) + jnp.sum(dy * dy, axis=-1) + eps)
    grad_sum = jnp.sum(mag * vf, axis=0)
    count = jnp.sum(vf, axis=0)
    return grad_sum, count


def temporal_mean_gradient(images: jax.Array, valid: jax.Array) -> jax.Array:
    g, c = grad_mag(images, valid)
    return g / jnp.maximum(c, 1.0)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality) chunked scan
# ---------------------------------------------------------------------------
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, d_skip: jax.Array | None = None) -> jax.Array:
    """Sequential-recurrence oracle for the SSD layer (Mamba-2, arXiv:2405.21060).

    x:  [B, L, H, P]   input sequences (H heads, P head dim)
    dt: [B, L, H]      softplus-activated step sizes (> 0)
    a:  [H]            negative state decay rate (A = -exp(a_log) outside)
    b:  [B, L, H, N]   input projection (per head; groups pre-broadcast)
    c:  [B, L, H, N]   output projection
    Returns y: [B, L, H, P].

    Recurrence per (batch, head):
        S_t = exp(a * dt_t) * S_{t-1} + dt_t * b_t x_t^T    (S: [N, P])
        y_t = c_t^T S_t
    """
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    bf, cf = b.astype(jnp.float32), c.astype(jnp.float32)
    af = a.astype(jnp.float32)
    B_, L, H, P = x.shape
    N = b.shape[-1]

    decay = jnp.exp(af[None, None, :] * dtf)  # [B, L, H]

    def step(S, inputs):
        dec_t, dt_t, b_t, c_t, x_t = inputs
        # S: [B, H, N, P]
        S = S * dec_t[..., None, None] + (
            dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :])
        y_t = jnp.einsum("bhn,bhnp->bhp", c_t, S)
        return S, y_t

    S0 = jnp.zeros((B_, H, N, P), jnp.float32)
    xs = (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0),
          jnp.moveaxis(xf, 1, 0))
    _, ys = jax.lax.scan(step, S0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # [B, L, H, P]
    if d_skip is not None:
        y = y + d_skip.astype(jnp.float32)[None, None, :, None] * xf
    return y.astype(x.dtype)


def ssd_scan_chunked(x, dt, a, b, c, *, chunk: int = 64,
                     d_skip: jax.Array | None = None) -> jax.Array:
    """Chunked (quadratic-intra, linear-inter) SSD — the algorithm the Pallas
    kernel implements, expressed in jnp.  Must equal `ssd_scan` to fp tolerance.
    """
    B_, L, H, P = x.shape
    N = b.shape[-1]
    if L % chunk:
        raise ValueError(f"L={L} not a multiple of chunk={chunk}")
    nc = L // chunk
    xf = x.astype(jnp.float32).reshape(B_, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(B_, nc, chunk, H)
    bf = b.astype(jnp.float32).reshape(B_, nc, chunk, H, N)
    cf = c.astype(jnp.float32).reshape(B_, nc, chunk, H, N)
    af = a.astype(jnp.float32)

    log_dec = af[None, None, None, :] * dtf          # [B, nc, Q, H]
    cum = jnp.cumsum(log_dec, axis=2)                 # inclusive cumsum
    total = cum[:, :, -1, :]                          # [B, nc, H]

    # intra-chunk: L_ij = exp(cum_i - cum_j) for i >= j (decay j -> i)
    li = cum[:, :, :, None, :]                        # [B,nc,Q,1,H]
    lj = cum[:, :, None, :, :]                        # [B,nc,1,Q,H]
    mask = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    L_mat = jnp.where(mask[None, None, :, :, None], jnp.exp(li - lj), 0.0)
    cb = jnp.einsum("bzihn,bzjhn->bzijh", cf, bf)     # [B,nc,Q,Q,H]
    y_intra = jnp.einsum("bzijh,bzjh,bzjhp->bzihp",
                         cb * L_mat, dtf, xf)

    # chunk states: S_z = sum_j exp(total - cum_j) dt_j b_j x_j^T
    dec_to_end = jnp.exp(total[:, :, None, :] - cum)  # [B,nc,Q,H]
    S_chunk = jnp.einsum("bzjh,bzjh,bzjhn,bzjhp->bzhnp",
                         dec_to_end, dtf, bf, xf)

    # inter-chunk scan of states
    def step(S, inp):
        tot_z, S_z = inp
        S_new = S * jnp.exp(tot_z)[..., None, None] + S_z
        return S_new, S  # emit state *entering* the chunk

    S0 = jnp.zeros((B_, H, N, P), jnp.float32)
    _, S_in = jax.lax.scan(
        step, S0, (jnp.moveaxis(total, 1, 0), jnp.moveaxis(S_chunk, 1, 0)))
    S_in = jnp.moveaxis(S_in, 0, 1)                   # [B,nc,H,N,P]

    # inter-chunk contribution: y_i += c_i^T (exp(cum_i) * S_in)
    y_inter = jnp.einsum("bzihn,bzih,bzhnp->bzihp",
                         cf, jnp.exp(cum), S_in)
    y = (y_intra + y_inter).reshape(B_, L, H, P)
    if d_skip is not None:
        y = y + d_skip.astype(jnp.float32)[None, None, :, None] * \
            x.astype(jnp.float32)
    return y.astype(x.dtype)
