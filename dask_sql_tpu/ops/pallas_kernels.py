"""MXU-native segment reductions for the hot aggregation path.

Scatter-add (`jax.ops.segment_sum`) serializes on the TPU's scatter unit —
and emulated 64-bit scatter is several times slower again.  The MXU-native
formulation is a one-hot matmul: `onehot(gid).T @ contribs`.  Two
implementations:

- `segsum_scan_blocked` — the production path.  Rows are processed in
  fixed-size blocks under `lax.fori_loop`; each step builds the block's
  one-hot in on-chip memory, runs ONE [domain, b] x [K, b] matmul on the MXU
  for ALL K contribution columns at once, and accumulates the per-block
  partial into a float64 carry.  A step reads its block as a contiguous
  [b] slice of each column and stacks the K slices row-minor, [K, b], in
  on-chip memory.  A stack of the whole columns would cost a pass over
  HBM in front of the loop (the TPU relays out each column to concatenate
  them), and a [blocks, b, K] view of it one more: eight blocks
  interleaved in every (8, 128) tile, so a relayout copy of the whole
  stack and a sublane-strided read per step.
  The per-block f64 accumulation bounds the f32 matmul-accumulation error
  to the block (measured: ~1e-7..1e-6 max relative on 6M uniform rows vs
  exact f64 — see tests/unit/test_pallas_kernels.py::
  test_segsum_scan_blocked_accuracy_and_counts, asserted at 5e-6); 0/1
  count columns are EXACT (integer-valued f32 partials below 2^24 per
  block, combined exactly in f64).  For float64 inputs the caller
  splits hi/lo (`split_hi_lo`) so representation error is ~2^-48.
- `segsum_pallas` — the same math as a hand-written pallas kernel (one-hot
  built only in VMEM).  Kept as an explicit opt-in probe
  (``sql.compile.segsum="pallas"``): a backend that cannot compile it fails
  the query rather than switching implementation.

`segsum_onehot_jnp` (single unblocked matmul) remains for reference and
verification; its f32 accumulation error grows with rows-per-segment, which
is why the blocked scan is the production path.

See /opt/skills/guides/pallas_guide.md for the pallas programming model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: error bound (max relative, float sums) the blocked matmul path is tested
#: to meet on-device; `choose_segsum_impl` only auto-selects modes meeting it
MATMUL_FLOAT_REL_ERR_BOUND = 5e-6

_DEFAULT_BLOCK = 32768


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def split_hi_lo(x64: jnp.ndarray):
    """Exact two-float32 decomposition of a float64 array (48-bit mantissa)."""
    hi = x64.astype(jnp.float32)
    lo = (x64 - hi.astype(jnp.float64)).astype(jnp.float32)
    return hi, lo


def segsum_onehot_jnp(gid: jnp.ndarray, contribs: jnp.ndarray, domain: int) -> jnp.ndarray:
    """[n] ids + [n, k] contributions -> [domain, k] sums via one one-hot matmul."""
    onehot = jax.nn.one_hot(gid, domain, dtype=contribs.dtype)
    return onehot.T @ contribs


def segsum_scan_blocked(gid: jnp.ndarray, cols, domain: int,
                        block: int = _DEFAULT_BLOCK) -> jnp.ndarray:
    """Blocked one-hot MXU segment sum with float64 partial accumulation.

    gid: [n] integer ids in [0, domain); cols: list of [n] float32 arrays
    (pre-masked: non-selected rows must carry 0).  Returns [domain, K]
    float64.  Works under jit tracing; block count is static.

    Each step slices its block of ``block`` rows out of every column (a
    contiguous read of each) and stacks the slices row-minor, ``[K, block]``,
    in on-chip memory: no ``[K, n]`` or ``[n, K]`` copy of the columns is
    ever built.  The rows past the last whole block are one more, shorter,
    partial: nothing is padded.
    """
    n = gid.shape[0]
    gid = gid.astype(jnp.int32)
    cols = [c.astype(jnp.float32) for c in cols]

    def partial(g, cs):  # [m] ids, K [m] columns -> [domain, K] float64
        onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (domain, g.shape[0]), 0)
                    == g[None, :]).astype(jnp.float32)
        return jax.lax.dot_general(
            onehot_t, jnp.stack(cs), dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).astype(jnp.float64)

    def step(i, carry):
        def block_of(x):
            return jax.lax.dynamic_slice_in_dim(x, i * block, block)

        return carry + partial(block_of(gid), [block_of(c) for c in cols])

    out = jnp.zeros((domain, len(cols)), dtype=jnp.float64)
    whole = n // block
    if whole:
        out = jax.lax.fori_loop(np.int32(0), np.int32(whole), step, out)
    if whole * block < n:
        tail = whole * block
        out = out + partial(gid[tail:], [c[tail:] for c in cols])
    return out


def segsum_pallas(gid: jnp.ndarray, contribs: jnp.ndarray, domain: int,
                  block_rows: int = 2048, interpret: bool = False) -> jnp.ndarray:
    """Pallas segment-sum: one-hot built per block in VMEM, MXU accumulate.

    gid: [n] int32 in [0, domain); contribs: [n, k] float32 (pre-masked).
    Returns [domain, k] float32 (f32 accumulation across the whole input —
    use segsum_scan_blocked when f64-bounded accuracy is required).
    """
    from jax.experimental import pallas as pl

    n, k = contribs.shape
    d_pad = max(_round_up(domain, 128), 128)
    k_pad = max(_round_up(k, 128), 128)
    # keep the VMEM-resident one-hot block within a ~4MB budget; the ids ride
    # a [1, b] lane-major block, so b is a multiple of the 128-lane tile
    budget_rows = (4 << 20) // (d_pad * 4)
    b = max(min(block_rows, budget_rows) // 128 * 128, 128)
    n_pad = max(_round_up(n, b), b)

    gid_p = jnp.zeros((1, n_pad), dtype=jnp.int32).at[0, :n].set(
        gid.astype(jnp.int32))
    # padded rows carry zero contributions, so their gid (0) adds nothing
    c_p = jnp.zeros((n_pad, k_pad), dtype=jnp.float32).at[:n, :k].set(
        contribs.astype(jnp.float32))

    def kernel(gid_ref, c_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        # transposed one-hot [d_pad, b]: lives only in VMEM, and the
        # accumulate below is a plain [d_pad, b] x [b, k_pad] MXU matmul
        onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (d_pad, b), 0)
                    == gid_ref[:]).astype(jnp.float32)
        out_ref[:] += jax.lax.dot_general(
            onehot_t, c_ref[:],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    # index maps return int32 explicitly: with x64 on, a Python 0 traces as
    # int64 and the chip's compiler refuses the mixed (i32, i64) return
    zero = np.int32(0)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // b,),
        in_specs=[
            pl.BlockSpec((1, b), lambda i: (zero, i)),
            pl.BlockSpec((b, k_pad), lambda i: (i, zero)),
        ],
        out_specs=pl.BlockSpec((d_pad, k_pad), lambda i: (zero, zero)),
        out_shape=jax.ShapeDtypeStruct((d_pad, k_pad), jnp.float32),
        interpret=interpret,
    )(gid_p, c_p)
    return out[:domain, :k]


def segsum_double_float(gid, contribs64, domain: int, use_pallas: bool = False,
                        interpret: bool = False) -> jnp.ndarray:
    """float64-in/out segment sum via hi/lo float32 columns.

    Kept for the explicit 'pallas' opt-in mode and verification.  hi/lo
    removes the f32 *representation* error (~2^-48); the remaining error is
    whole-input f32 accumulation (measured ~2e-5 max relative at 6M rows,
    domain 16 — NOT the blocked bound; prefer segsum_scan_blocked).
    """
    x = contribs64.astype(jnp.float64)
    hi, lo = split_hi_lo(x)
    n, k = x.shape
    stacked = jnp.concatenate([hi, lo], axis=1)  # [n, 2k]
    if use_pallas:
        out = segsum_pallas(gid, stacked, domain, interpret=interpret)
    else:
        out = segsum_onehot_jnp(gid, stacked, domain)
    return out[:, :k].astype(jnp.float64) + out[:, k:].astype(jnp.float64)


def choose_segsum_impl(config, domain: int) -> str:
    """'scatter' | 'matmul' | 'pallas' based on config + platform + domain.

    auto: the blocked MXU matmul ('matmul') where it meets
    MATMUL_FLOAT_REL_ERR_BOUND and the one-hot FLOPs stay cheap (small
    domains); exact scatter otherwise.  Counts and int sums are exact in
    every mode (matmul counts are integer-valued f32 partials < 2^24 /
    block combined in f64; int sums always use int64 scatter)."""
    mode = str(config.get("sql.compile.segsum", "auto"))
    if mode in ("scatter", "matmul", "pallas"):
        # explicit requests are honoured as-is: a 'pallas' kernel the
        # backend cannot compile fails the query, it never becomes 'matmul'
        return mode
    if mode != "auto":
        raise ValueError(
            f"sql.compile.segsum must be auto/scatter/matmul/pallas, got {mode!r}")
    platform = jax.devices()[0].platform
    if platform == "tpu" and domain <= 2048:
        return "matmul"
    return "scatter"
