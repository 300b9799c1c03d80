"""Selection math shared by the fused Pallas sweep kernel and its jnp oracle.

Backend-parity tests require *exact* trajectory agreement between
``kernels.sweep.mcmc_sweep`` and ``kernels.ref.mcmc_sweep``, so every piece of
per-step arithmetic whose floating-point association matters — flip
probability (exact or PWL LUT), the hierarchical roulette scan, and the
site-index rescaling — lives here as pure jnp functions on values. The kernel
reads its VMEM refs into values and calls these; the oracle calls the same
functions from a ``lax.scan``. Both therefore trace to identical op sequences.

Everything here is also written so that Mosaic (the TPU Pallas compiler) can
lower it inside the kernel: per-replica quantities are (R, 1) columns, values
are moved by static lane slices and masked reductions (never by value-level
``dynamic_slice`` or ``gather``), and the roulette's prefix sums are a
log-depth doubling scan of shifts and adds in place of ``cumsum``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import rng
from ..core.bitplane import WORD_BITS

#: Widest lane block considered for the hierarchical roulette scan. 128 is the
#: TPU lane count — a within-block scan over ≤128 lanes stays in-register.
MAX_LANE = 128

#: The TPU vreg tile, (sublanes, lanes). A Pallas block's last two dims must be
#: multiples of it or span the whole array, and a dynamic lane offset must be
#: a multiple of :data:`LANE_TILE`.
SUBLANE_TILE = 8
LANE_TILE = 128

#: Ceiling for a kernel's scoped-VMEM request: a v5e TensorCore has 128 MiB of
#: VMEM, and the compiler keeps some for its own spills.
VMEM_LIMIT_CAP = 112 * 2**20


def replica_block(r: int, target: int) -> int:
    """Replica rows per grid program: the largest divisor of ``r`` that is
    ≤ ``target`` and a multiple of :data:`SUBLANE_TILE`, else all of ``r``.
    Either choice is a legal TPU block for every (…, R, ·) operand at any R;
    a block of 6 of 12 replicas would not be."""
    for b in range(min(target, r), 0, -1):
        if r % b == 0 and b % SUBLANE_TILE == 0:
            return b
    return r


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of one buffer: the last two dims pad to the (8, 128)
    tile (sub-32-bit dtypes pack more rows per tile, which this ignores —
    an over-estimate is the safe side of a limit)."""
    *lead, rows, cols = (1, 1, *shape)
    size = round_up(rows, SUBLANE_TILE) * round_up(cols, LANE_TILE)
    for d in lead:
        size *= d
    return size * jnp.dtype(dtype).itemsize


def vmem_limit(nbytes: int) -> int:
    """``vmem_limit_bytes`` for a kernel whose buffers need ``nbytes``: a 25%
    + 8 MiB allowance for the compiler's temporaries (the sweep's (br, N)
    step values), at least the 32 MiB default, at most
    :data:`VMEM_LIMIT_CAP`. A request past the cap is left for Mosaic to
    refuse — the error propagates, it never selects another tier."""
    return int(min(max(nbytes * 5 // 4 + 8 * 2**20, 32 * 2**20),
                   VMEM_LIMIT_CAP))


def decode_bitplane_rows(pos: jax.Array, neg: jax.Array, n: int) -> jax.Array:
    """Decode packed signed bit-plane words into f32 coupling rows (Eq. 13).

    ``pos``/``neg``: (B, ..., W) uint32 — the W packed words of one J row per
    plane (kernel: a (B, 1, W) ``pl.ds`` slice of the VMEM-resident planes;
    oracle: a (B, R, W) ``jnp.take`` gather). Returns (..., n) float32 via
    J_row = Σ_b 2^b (bits(pos_b) − bits(neg_b)). The expansion is a plain
    shift-and-mask over the 32 bit positions plus an unrolled weighted sum
    over the B planes — O(B·N) VPU work, no ``dot_general`` (the fused
    sweep's jaxpr pin covers this path too) and no ``population_count``
    (the row update needs the individual coupler bits, not their weight).
    Plane values are small integers, so the f32 row is exact.
    """
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)

    def expand(words):  # (..., W) uint32 -> (..., W·32) {0,1} int32, LSB-first
        bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
        return bits.reshape(words.shape[:-1] + (-1,)).astype(jnp.int32)

    num_planes = pos.shape[0]
    row = jnp.zeros(pos.shape[1:-1] + (pos.shape[-1] * WORD_BITS,), jnp.float32)
    for b in range(num_planes):  # static unroll: B is small (≤ 16)
        diff = expand(pos[b]) - expand(neg[b])
        row = row + jnp.float32(1 << b) * diff.astype(jnp.float32)
    return row[..., :n]


def default_lane(n: int) -> int:
    """Largest divisor of ``n`` that is ≤ MAX_LANE (BlockSpec-exact tiling).

    The roulette wheel over N sites is scanned as G = N/L block sums followed
    by one L-wide within-block scan, replacing the O(N)-deep flat scan with
    two short ones."""
    for lane in range(min(MAX_LANE, n), 0, -1):
        if n % lane == 0:
            return lane
    return 1


def default_pwl_select() -> str:
    """How the PWL LUT segment is evaluated when the caller does not say:
    "select" (the lane-friendly compare-and-select sweep) on real TPUs, where
    a per-element gather serializes lane-by-lane on the VPU; "gather" (two
    ``jnp.take``s) everywhere else, where gathers are cheap and the S-deep
    select sweep is pure overhead. The two are bit-identical, so the choice
    can never split backend parity; the Pallas kernels always pass "select"
    (the only one Mosaic lowers)."""
    return "select" if jax.default_backend() == "tpu" else "gather"


def flip_probability(delta_e: jax.Array, temperature: jax.Array,
                     pwl_table: jax.Array | None = None,
                     pwl_select: str | None = None) -> jax.Array:
    """Glauber flip probability σ(-ΔE/T) (exact or PWL LUT).

    ``pwl_table`` is the ``(S+1, 3)`` ``[knot, value, slope]`` LUT from
    :func:`repro.core.pwl.pwl_table` (None = exact sigmoid) — the same
    construction as ``core.pwl.make_pwl_sigmoid``, evaluated in intercept
    form (agrees with the reference PWL to float ulps; kernel and oracle
    share THIS function, so backend parity stays exact). T ≤ 0 uses the
    greedy limit (1 downhill / 0.5 flat / 0 uphill). Broadcasts over any
    leading shape.

    ``pwl_select`` picks the LUT evaluation: "gather" reads
    ``icpt[seg]``/``slopes[seg]`` with two per-element ``jnp.take``s;
    "select" sweeps the S segments, statically unrolled, with branch-free
    compare-and-select (``where(seg == k, icpt_k, …)``), trading O(S·N) VPU
    selects for zero gathers. The two are **bit-identical** by construction:
    exactly one segment matches per element and the selected lane computes
    the same ``icpt + slope·z`` FMA the gather path computes (asserted
    exactly by ``tests/test_kernels.py``). None resolves via
    :func:`default_pwl_select`. The coefficients are computed on (S+1, 1)
    columns and read out as scalars by static index, so the select path
    lowers under Mosaic.
    """
    de = delta_e.astype(jnp.float32)
    t = jnp.asarray(temperature, jnp.float32)
    safe_t = jnp.where(t > 0, t, 1.0)
    z = -de / safe_t
    if pwl_table is None:
        warm = jax.nn.sigmoid(z)
    else:
        if pwl_select is None:
            pwl_select = default_pwl_select()
        if pwl_select not in ("gather", "select"):
            raise ValueError(f"pwl_select must be 'gather' or 'select', "
                             f"got {pwl_select!r}")
        num_segments = pwl_table.shape[0] - 1
        knots = pwl_table[:, 0:1]                          # (S+1, 1)
        # Intercept form y = icpt[seg] + slope[seg]·z (last slope row is the
        # zero padding; its intercept is never selected).
        icpt = pwl_table[:, 1:2] - pwl_table[:, 2:3] * knots
        z_lo = knots[0, 0]
        z_hi = knots[num_segments, 0]
        inv_step = (jnp.float32(1.0) / (knots[1:2] - knots[0:1]))[0, 0]
        zc = jnp.clip(z, z_lo, z_hi)  # tails collapse into the end segments
        seg = jnp.clip(((zc - z_lo) * inv_step).astype(jnp.int32),
                       0, num_segments - 1)
        if pwl_select == "gather":
            seg_icpt = jnp.take(icpt[:num_segments, 0], seg)
            seg_slope = jnp.take(pwl_table[:num_segments, 2], seg)
        else:
            # The sweep only *moves* coefficients (branch-free selects, no
            # arithmetic), so it is value-exact vs the gather; the y = icpt +
            # slope·z FMA below is then the structurally identical array
            # expression in both formulations — were it computed inside the
            # loop on scalar coefficients, the compiler could contract it to
            # an fma there but not in the gather path, splitting last-ulp
            # parity (observed on XLA CPU).
            seg_icpt = jnp.zeros_like(zc)
            seg_slope = jnp.zeros_like(zc)
            for k in range(num_segments):
                hit = seg == k
                seg_icpt = jnp.where(hit, icpt[k, 0], seg_icpt)
                seg_slope = jnp.where(hit, pwl_table[k, 2], seg_slope)
        warm = seg_icpt + seg_slope * zc
    cold = jnp.where(de < 0, 1.0, jnp.where(de == 0, 0.5, 0.0))
    return jnp.where(t > 0, warm, cold).astype(jnp.float32)


def take_lane(x: jax.Array, k) -> jax.Array:
    """``x[:, k:k+1]`` for a traced lane index ``k`` by masked reduction —
    exact (one term plus zeros) and lowerable where a value-level
    ``dynamic_slice`` is not."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lanes == k, x, jnp.zeros((), x.dtype)), axis=1,
                   keepdims=True)


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along axis 1 of an (R, K) array, by a log-depth
    doubling scan (Hillis–Steele): at level k every lane adds the lane 2^k
    to its left (zeros shifted in past lane 0), ⌈log₂ K⌉ levels statically
    unrolled. The association is fixed by the levels — not left to right,
    so a prefix can sit an ulp below its left neighbour even for
    non-negative ``x`` (the picks below take the first crossing, which does
    not need monotone prefixes). Every backend — XLA CPU, XLA TPU, Mosaic —
    adds in this order and produces the same bits; the roulette's kernel
    and oracle share it. It stands in for ``cumsum``, which Mosaic does not
    lower."""
    r, width = x.shape
    step = 1
    while step < width:
        left = jnp.concatenate([jnp.zeros((r, step), x.dtype),
                                x[:, :width - step]], axis=1)
        x = x + left
        step *= 2
    return x


def first_crossing(c: jax.Array, radius: jax.Array) -> jax.Array:
    """(R, 1) index of the first lane of the (R, K) prefixes ``c`` that
    exceeds ``radius``, clamped to K − 1 (no crossing: W ≤ radius by
    rounding, or a NaN wheel). A masked lane-min, branch-free; it equals
    the ≤-count ``Σ(c ≤ radius)`` wherever ``c`` is monotone, and every
    lane before it has ``c ≤ radius``, so the residual into it is ≥ 0."""
    num = c.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    return jnp.minimum(jnp.min(jnp.where(c > radius, lanes, num), axis=1,
                               keepdims=True), num - 1)


def block_sums(p: jax.Array, lane: int) -> jax.Array:
    """(R, G) sums of each ``lane``-wide block of the (R, G·lane) weights.

    The order of the additions is fixed here, not left to a compiler's
    reduction: a doubling tree of elementwise adds on the whole row — at
    level k every lane adds the lane 2^k to its right when that lane is in
    the same block — leaves block g's sum in its first lane. XLA:CPU,
    XLA:TPU and Mosaic therefore produce the same bits, and so do the
    single-device pick and each shard of the spin-sharded pick (a block
    sums the same way wherever its lanes live)."""
    r, width = p.shape
    num_blocks = width // lane
    pos = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) % lane
    x = p
    step = 1
    while step < lane:
        right = jnp.concatenate([x[:, step:], jnp.zeros((r, step), p.dtype)],
                                axis=1)
        x = x + jnp.where(pos + step < lane, right, jnp.zeros((), p.dtype))
        step *= 2
    iota_g = jax.lax.broadcasted_iota(jnp.int32, (r, num_blocks), 1)
    blk = jnp.zeros((r, num_blocks), p.dtype)
    for g in range(num_blocks):  # static unroll: G = N/lane
        blk = jnp.where(iota_g == g, x[:, g * lane:g * lane + 1], blk)
    return blk


def select_block(p: jax.Array, g: jax.Array, lane: int,
                 g0=0) -> jax.Array:
    """(R, lane) lanes of block ``g[r]`` of the (R, G·lane) weights, zeros
    where the block is not among this array's blocks ``g0 …`` (the sharded
    pick's psum then combines owners exactly: v + 0 + … + 0 = v)."""
    r, width = p.shape
    sel = jnp.zeros((r, lane), p.dtype)
    for k in range(width // lane):  # static unroll, pure selection
        sel = jnp.where(g == g0 + k, p[:, k * lane:(k + 1) * lane], sel)
    return sel


def roulette_block_pick(blk: jax.Array, u_roulette: jax.Array):
    """Level-1 of the hierarchical roulette: pick the winning block from the
    (R, G) block-weight sums; ``u_roulette`` is an (R, 1) column. Returns
    (R, 1) columns ``(g, residual, total, degenerate)``.

    Split out of :func:`roulette_pick` so the spin-sharded driver can run the
    identical arithmetic on an all-gathered ``blk`` — the block pick is a
    pure function of the block sums, so sharded and single-device trajectories
    stay exactly equal (the parity contract of this module's docstring).
    """
    num_blocks = blk.shape[1]
    cb = prefix_sum(blk)                           # (R, G) log-depth scan
    total = cb[:, num_blocks - 1:]                 # W (Eq. 28)
    degenerate = ~((total > 0) & (total < jnp.inf))   # W ≤ 0, inf or NaN
    radius = u_roulette * jnp.where(degenerate, 1.0, total)
    g = first_crossing(cb, radius)                 # block index (R, 1)
    base = take_lane(cb, g - 1)                   # exclusive prefix; 0 at g=0
    return g, radius - base, total, degenerate


def roulette_lane_pick(sel: jax.Array, residual: jax.Array):
    """Level-2 of the hierarchical roulette: the within-block lane pick from
    the (R, lane) selected-block weights (sharded callers psum-combine
    ``sel`` from the block owner; the arithmetic is shared either way)."""
    return first_crossing(prefix_sum(sel), residual)


def roulette_pick(p_all: jax.Array, u_roulette: jax.Array, lane: int):
    """Hierarchical roulette-wheel selection (paper Eq. 28-29).

    ``p_all`` is (R, N); ``u_roulette`` an (R, 1) column in [0,1). Returns
    (R, 1) columns ``(site, total, degenerate)``. Site ``j`` is drawn with
    probability ``p_j / W`` via a two-level scan: a prefix sum over the
    G = N/lane block sums picks the block, a lane-wide prefix sum inside the
    selected block picks the site — O(log G + log lane) scan depth, with no
    loop. Each level takes the first crossing (:func:`first_crossing`),
    branch-free.
    """
    blk = block_sums(p_all, lane)                  # (R, G) block weights
    g, residual, total, degenerate = roulette_block_pick(blk, u_roulette)
    l = roulette_lane_pick(select_block(p_all, g, lane), residual)
    return (g * lane + l).astype(jnp.int32), total, degenerate


def site_from_uniform(u01: jax.Array, n: int) -> jax.Array:
    """Random-scan site pick — the canonical ``core.rng`` rescaling (Eq. 22)."""
    return rng.index_from_uniform(u01, n)


def _col_to_row(col: jax.Array) -> jax.Array:
    """(R, 1) → (1, R) by masked sublane reduction (an exact transpose)."""
    r = col.shape[0]
    rr = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    return jnp.sum(jnp.where(rr == cc, col, jnp.zeros((), col.dtype)), axis=0,
                   keepdims=True)


def coalesce_rows(j: jax.Array):
    """Duplicate structure of one step's selected sites, an (R, 1) column —
    the reuse-aware row-fetch plan shared by the HBM-streamed kernel and the
    spin-sharded driver (R fetches/step → unique(R) fetches/step).

    Returns ``(nu, usite, uo, fetched)``, the last three (R, 1) int32:

    * ``nu``      — scalar int32, the number of *unique* sites in ``j``
                    (1 ≤ nu ≤ R; nu row fetches replace R).
    * ``usite``   — the m-th unique site in first-occurrence order for
                    m < nu (entries at m ≥ nu repeat the first site
                    harmlessly — fetch loops run ``nu`` iterations).
    * ``uo``      — each replica's index into the unique list
                    (``usite[uo[r]] == j[r]`` for every r), so the decoded
                    unique rows broadcast back to every replica that
                    selected them.
    * ``fetched`` — one-hot-per-group fetch attribution: 1 on the
                    lowest-index replica of each duplicate group, 0 on the
                    replicas reusing its row (``sum(fetched) == nu`` — the
                    per-step unique-rows-fetched counter).

    The decoded row is a deterministic function of the site alone, so
    fetch-once-broadcast is byte-identical to fetch-per-replica — coalescing
    can never move a trajectory. Everything is O(R²) masked reductions over
    2-D ``broadcasted_iota`` — no ``sort``, no ``cumsum``, no 1-D values —
    so the identical code runs inside the Pallas kernel and in the
    shard_map'd jnp driver.
    """
    r = j.shape[0]
    rr = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)   # row ids
    cc = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)   # column ids
    rid = rr[:, :1]                                        # (R, 1) 0..R-1
    eq = j == _col_to_row(j)                               # (R, R)
    # first_idx[r]: lowest replica index selecting the same site as r.
    first_idx = jnp.min(jnp.where(eq, cc, r), axis=1, keepdims=True)
    is_first = first_idx == rid
    fetched = is_first.astype(jnp.int32)
    first_row = _col_to_row(fetched) > 0                   # (1, R)
    # Position of each first occurrence in the compacted unique list
    # (inclusive prefix count of firsts, minus one), via a masked 2-D sum.
    uo_first = jnp.sum(jnp.where((cc <= rr) & first_row, 1, 0), axis=1,
                       keepdims=True) - 1
    uo_row = _col_to_row(uo_first)
    uo = jnp.sum(jnp.where(cc == first_idx, uo_row, 0), axis=1, keepdims=True)
    nu = jnp.sum(fetched)
    usite = jnp.sum(jnp.where((uo_row == rid) & first_row, _col_to_row(j), 0),
                    axis=1, keepdims=True)
    # Fetch loops index usite at m < nu only; park the tail on a valid site
    # so a clamped prefetch can never read out of range.
    usite = jnp.where(rid < nu, usite, jnp.sum(jnp.where(rid == 0, usite, 0)))
    return nu, usite, uo, fetched
