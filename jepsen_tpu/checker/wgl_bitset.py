"""Exact bitset-automaton Pallas kernel for the WGL linearizability scan.

The K-frontier kernels (wgl_jax.py, wgl_pallas.py) approximate the
config set with a fixed-capacity table and pay for dedup / dominance
pruning with [K, W, K] all-pairs compares every closure round. For the
windows real register workloads produce (W <= 16 open ops), the ENTIRE
config space is small enough to hold exactly:

    config = (state row, linearized-slot mask)
    space  = S rows x 2^W masks,   S = interned value codes + 1

so the frontier becomes a [S, 2^W] BIT TENSOR, lane-packed 32 masks per
int32 word ([S, 2^W/32] int32 in VMEM: W=16, S=8 -> 64 KB). This
representation is exact — no capacity, no overflow, no escalation
ladder, no dedup (set semantics are free: a config is a bit), and no
dominance pruning (nothing ever needs to be evicted).

A closure round linearizes each open window slot w against every config
at once as three cheap whole-tensor ops:

  1. source rows:  read/cas fire from one state row (a one-hot sublane
     select); write fires from the union of all rows (a log-tree OR);
  2. "add slot bit w" relabeling: masks without bit w map to masks with
     it — for w < 5 an in-word masked shift by 2^w, for w >= 5 a masked
     lane roll by 2^(w-5) words (pltpu.roll — mask bit w lives 2^(w-5)
     words away at the same bit position);
  3. destination scatter: OR into the dst state row (one-hot sublane
     broadcast).

Slots chain within a round (in-place monotone OR), so fixpoint arrives
in <= W rounds; the usual case is 2 (one productive + one verification).
The RETURN filter is the inverse relabeling with a *dynamic* slot
index: keep masks containing the returning bit, shift them back
(dynamic-shift roll), which also frees the slot for reuse.

Soundness: every set bit is a config reached by a legal linearization
chain that passed every prior RETURN filter (monotone ORs only add
reachable configs). Two execution tiers share this invariant:

- FAST tier (default): FAST_ROUNDS unrolled closure rounds per step,
  no convergence checks — chains deeper than the budget leave the
  frontier UNDER-closed, i.e. a subset of the true config set.
  alive=True is still definite (any surviving config is a witness);
  alive=False is provisional, and the driver escalates it.
- EXACT tier: adaptive while_loop to a verified fixpoint (round bound
  W+2 exceeds the longest possible chain; non-convergence — impossible
  by that argument — still reports as taint rather than trusting the
  verdict). Both verdicts definite; used to decide fast-tier deaths,
  so a reported failure's op_index is always the exact tier's.

The tiering exists because the while_loop machinery costs ~1.8 us/step
of scalar-core serialization on v5e while the unrolled rounds cost
~0.2 us at W=12 — and valid histories (the overwhelmingly common case)
never leave the fast tier.

Reference role: the knossos search behind
jepsen/src/jepsen/checker.clj:127-158, as an exact accelerator-resident
automaton instead of a JVM graph search.
"""

from __future__ import annotations

import functools
import threading
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jepsen_tpu.checker.events import ReturnSteps, bucket, memo_on
from jepsen_tpu.checker.models import model as get_model
from jepsen_tpu.obs import trace as obs_trace

#: out columns: alive, taint, died op index, rounds total, rounds max
OUT_COLS = 8

#: per-step meta columns: slot, live, op_index, fresh mask (the init
#: state travels as the fr_in frontier, not per-step meta)
META_COLS = 4

#: return-steps per grid iteration (amortizes per-iteration block DMA
#: and grid sequencing; B=16 measured ~15% faster than 8 on the
#: north-star scan, B=32 no better and twice the compile time)
STEP_BLOCK = 16


def step_block(W: int, interpret: bool = False) -> int:
    """Substeps per grid iteration: 1 at W=20 — the unrolled kernel
    body over 32768-lane tensors is otherwise too much program for
    Mosaic to compile in reasonable time. Interpret mode (CPU tests)
    uses a small block: the fully unrolled fast-tier body at B=16
    builds an HLO graph deep enough to crash XLA:CPU's compiler
    (observed segfault in backend_compile_and_load); Mosaic on real
    TPU handles the full block."""
    if interpret:
        return 4
    # Wide windows keep a smaller block (compile time grows with the
    # unrolled body x lane count), but at least 8: a 1-step block's
    # meta BlockSpec (1, 1, META_COLS) violates the TPU lowering's
    # sublane-divisibility rule.
    return STEP_BLOCK if W <= 16 else 8

#: mask-word lane floor: smaller windows still use full vector lanes
MIN_WORDS = 128

#: supported window buckets (2^W/32 words: 128..16384 lanes). Per-step
#: vector cost scales with 2^W once the per-step machinery is paid, so
#: every width is its own bucket and the segment planner moves between
#: them as the live window fluctuates (measured on v5e: the
#: leading-prefix-only W12/W16 split left 25k+ of the north star's
#: steps running 16x too wide). W=17-19 compile in 27-95 s on the fast
#: tier (cached thereafter) and keep crash-heavy tails EXACT on device
#: at ~37-120 us/step — still ahead of the native C++ oracle's ~90
#: us/step, and far ahead of the K-frontier ladder's
#: escalate-then-oracle path these windows previously took. W=20 was
#: attempted and abandoned: Mosaic does not finish compiling the
#: closure kernel over 32768-lane tensors in any reasonable time
#: (>10 min), so windows past 19 route to the K-frontier ladder.
W_BUCKETS = (12, 13, 14, 15, 16, 17, 18, 19)

#: state-row (S) padding quantum (documented default; live value
#: resolves through the perf knob registry, "wgl_bitset.
#: rows_bucket_growth")
ROWS_BUCKET_GROWTH = 8


def _w_buckets() -> tuple:
    """The active W rung ladder ("wgl_bitset.w_buckets"): the
    persisted per-backend profile's choice when one is loaded, the
    live W_BUCKETS module constant otherwise (so tests that prepend
    narrow rungs keep working). Every ladder the registry admits tops
    out at 19 (the Mosaic compile ceiling), so the envelope gate's
    semantics never move — only which rungs get compiled."""
    from jepsen_tpu.perf import knobs as _perf_knobs

    return tuple(_perf_knobs.resolve("wgl_bitset.w_buckets", W_BUCKETS))

#: state-row cap (VMEM: 32 x 2048 x 4 B = 256 KB at W=16)
MAX_ROWS = 32

#: VMEM budget for the two [S, M] frontier scratches (v5e scoped vmem
#: is ~16 MiB; at W=20 this caps S at 16 rows)
_VMEM_BYTES = 4 * 1024 * 1024

_U = np.uint32
#: in-word mask-bit patterns: _C1[k] has bit beta set iff beta & (1<<k)
_C1 = tuple(
    int(np.int32(_U(sum(1 << b for b in range(32) if b & (1 << k)))))
    for k in range(5)
)


def w_bucket(window: int) -> int | None:
    for w in _w_buckets():
        if window <= w:
            return w
    return None


def _rows_bucket(rows: int) -> int:
    from jepsen_tpu.perf import knobs as _perf_knobs

    g = max(
        int(
            _perf_knobs.resolve(
                "wgl_bitset.rows_bucket_growth", ROWS_BUCKET_GROWTH
            )
        ),
        1,
    )
    return max(g, bucket(rows, g))


def plan(m, window: int, n_value_codes: int) -> Tuple[int, int] | None:
    """(W, S) kernel shape for a model + history envelope, or None when
    the stream is outside the bitset kernel's envelope (window too wide,
    too many state rows, or a model without slot transitions). The ONE
    gate both the single-key driver and the key-batch path consult."""
    if m.bitset_slot_jax is None:
        return None
    W = w_bucket(max(window, 1))
    if W is None:
        return None
    S = _rows_bucket(m.bitset_rows(n_value_codes))
    if S > MAX_ROWS:
        return None
    if 2 * 4 * S * bitset_words(W) > _VMEM_BYTES:
        return None  # frontier scratches would blow scoped VMEM
    return W, S


def _or_rows(fr, S: int):
    """[S, M] -> [1, M] bitwise-OR over state rows (log tree)."""
    x = fr
    s = S
    while s > 1:
        h = s // 2
        x = x[:h] | x[h : 2 * h]
        s = h
    return x


def _add_bit(src, w: int, lane):
    """Relabel masks m -> m | bit(w) for a static slot w: sources are
    masks WITHOUT the bit; everything else contributes zero."""
    if w < 5:
        keep = jnp.int32(~np.int32(_C1[w]))
        return (src & keep) << (1 << w)
    sel = ((lane >> (w - 5)) & 1) == 0
    return pltpu.roll(jnp.where(sel, src, 0), 1 << (w - 5), 1)


def _remove_bit_dyn(fr, r, lane, M: int):
    """Relabel masks m -> m & ~bit(r) keeping only masks WITH bit r, for
    a dynamic returning slot r (the RETURN filter)."""
    # In-word branch (r < 5): pattern constant selected by r, masked
    # right-shift by 2^r.
    c1 = jnp.int32(_C1[0])
    for k in range(1, 5):
        c1 = jnp.where(r == k, jnp.int32(_C1[k]), c1)
    sh = jnp.left_shift(jnp.int32(1), jnp.minimum(r, 4))
    # logical, not arithmetic: word bit 31 is a real mask bit, and an
    # arithmetic >> would smear it across the word
    intra = lax.shift_right_logical(fr & c1, sh)
    # Word branch (r >= 5): lane roll back by 2^(r-5) words.
    wb = jnp.maximum(r - 5, 0)
    sel = ((lane >> wb) & 1) == 1
    shift = jnp.int32(M) - jnp.left_shift(jnp.int32(1), wb)
    word = pltpu.roll(jnp.where(sel, fr, 0), shift, 1)
    return jnp.where(r < 5, intra, word)


#: fast-tier fixed closure rounds (round 0 counts): covers chain
#: depth <= FAST_ROUNDS. Deeper chains under-close the frontier, which
#: is SOUND for alive verdicts (subset of the true closure — every set
#: bit is still a legal linearization witness) and merely triggers the
#: exact-kernel re-run when the fast tier reports a death.
FAST_ROUNDS = 3


def _make_kernel(model_name: str, S: int, W: int, exact: bool = True,
                 interpret: bool = False):
    bitset_slot = get_model(model_name).bitset_slot_jax
    assert bitset_slot is not None, model_name
    M = max((1 << W) // 32, MIN_WORDS)
    B = step_block(W, interpret)

    def kernel(win_ref, meta_ref, fr_in_ref, out_ref, fr_out_ref,
               f_ref, snap_ref):
        # Grid: (keys, step-blocks); steps iterate fastest, so the
        # per-key frontier resets at each key's first block.
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            # Start from the caller-provided frontier (segment chaining
            # hands the previous segment's final frontier in; a fresh
            # scan passes the single init-state config).
            f_ref[:] = fr_in_ref[0]
            out_ref[0, 0, 0] = 1  # alive
            out_ref[0, 0, 1] = 0  # taint (unconverged closure; never)
            out_ref[0, 0, 2] = -1  # died op index
            out_ref[0, 0, 3] = 0  # total closure rounds (debug)
            out_ref[0, 0, 4] = 0  # max closure rounds in one step (debug)
            out_ref[0, 0, 5] = 0
            out_ref[0, 0, 6] = 0
            out_ref[0, 0, 7] = 0

        for b in range(B):
            _substep(win_ref, meta_ref, out_ref, fr_out_ref, f_ref,
                     snap_ref, b)

        @pl.when(
            (i == pl.num_programs(1) - 1) & (out_ref[0, 0, 0] == 1)
        )
        def _final():
            # alive only: a death already wrote its pre-filter
            # frontier artifact into fr_out
            fr_out_ref[0] = f_ref[:]

    def _round_body(f, b, win_ref, fresh, r, lane1, rows):
        """One closure round over all W slots, branch-free: measured
        on v5e, every pl.when/loop branch costs ~200 ns of scalar-core
        serialization, and a per-slot pl.when design spent ~50
        branches (~10 us) per step with the vector units idle —
        per-step wall was FLAT in M. Slot gating is therefore
        arithmetic (a gated-out slot contributes zero to the OR)."""
        for w in range(W):
            occw = win_ref[0, b, 0, w]
            freshw = (fresh >> w) & 1
            gate = jnp.where(r == 0, freshw, occw)
            fw = win_ref[0, b, 1, w]
            aw = win_ref[0, b, 2, w]
            bw = win_ref[0, b, 3, w]
            is_union, src_row, dst_row, valid = bitset_slot(fw, aw, bw)
            one_row = jnp.sum(
                jnp.where(rows == src_row, f, 0),
                axis=0,
                keepdims=True,
            )
            union = _or_rows(f, S)
            src = jnp.where(is_union, union, one_row)
            src = jnp.where(valid & (gate == 1), src, 0)
            add = jnp.where(rows == dst_row, _add_bit(src, w, lane1), 0)
            f = f | add
        return f

    def _substep(win_ref, meta_ref, out_ref, fr_out_ref, f_ref,
                 snap_ref, b):
        slot_r = meta_ref[0, b, 0]
        live = meta_ref[0, b, 1]
        opidx = meta_ref[0, b, 2]
        alive = out_ref[0, 0, 0]

        fresh = meta_ref[0, b, 3]

        # Round 0 expands ONLY freshly invoked slots: the frontier
        # arrives closed under every other open op (a RETURN filter
        # preserves closure — events.ReturnSteps.fresh), so further
        # rounds run only to chase chains round 0 enabled. Steps with
        # no fresh invokes skip the closure entirely.
        #
        # EXACT tier: adaptive while_loop to a verified fixpoint —
        # definite verdicts both ways, but the loop machinery costs
        # ~1.8 us/step of scalar-core serialization (measured v5e).
        #
        # FAST tier: FAST_ROUNDS unrolled rounds, no convergence
        # check. Chains deeper than FAST_ROUNDS leave the frontier
        # UNDER-closed — a subset of the true config set, since
        # monotone ORs only ever add legally-reached configs. alive=1
        # is therefore still a definite VALID (any surviving config is
        # a witness); alive=0 is NOT definite (the dropped configs
        # might have survived), so the driver re-runs the dying
        # segment on the exact tier before reporting invalid.
        @pl.when((alive == 1) & (live == 1) & (fresh != 0))
        def _rounds():
            lane1 = lax.broadcasted_iota(jnp.int32, (1, M), 1)
            rows = lax.broadcasted_iota(jnp.int32, (S, 1), 0)

            if not exact:
                f = f_ref[:]
                for r in range(FAST_ROUNDS):
                    f = _round_body(
                        f, b, win_ref, fresh, jnp.int32(r), lane1, rows
                    )
                f_ref[:] = f
                return

            def round_fn(st):
                _, r = st
                snap_ref[:] = f_ref[:]
                f = _round_body(
                    f_ref[:], b, win_ref, fresh, r, lane1, rows
                )
                f_ref[:] = f
                changed = jnp.any(f != snap_ref[:])
                return changed, r + 1

            def cond_fn(st):
                changed, r = st
                return changed & (r <= W + 2)

            changed, nr = lax.while_loop(
                cond_fn, round_fn, (jnp.bool_(True), jnp.int32(0))
            )
            out_ref[0, 0, 3] = out_ref[0, 0, 3] + nr
            out_ref[0, 0, 4] = jnp.maximum(out_ref[0, 0, 4], nr)

            @pl.when(changed)
            def _taint():  # round bound hit (see module docstring)
                out_ref[0, 0, 1] = 1

        @pl.when((alive == 1) & (live == 1))
        def _ret():
            lane1 = lax.broadcasted_iota(jnp.int32, (1, M), 1)

            # RETURN filter: keep configs with the returning op
            # linearized, clear its bit (frees the slot).
            pre = f_ref[:]
            fr = _remove_bit_dyn(pre, slot_r, lane1, M)
            f_ref[:] = fr

            @pl.when(jnp.logical_not(jnp.any(fr != 0)))
            def _died():
                out_ref[0, 0, 0] = 0
                out_ref[0, 0, 2] = opidx
                # Failure artifact: the competing configs the filter
                # killed — every state/mask the search still considered
                # possible when the returning op proved impossible
                # (checker.clj:146-154's reporting role). On the fast
                # tier this is provisional — the exact re-run decides.
                fr_out_ref[0] = pre

    return kernel, M


def bitset_words(W: int) -> int:
    return max((1 << W) // 32, MIN_WORDS)


#: host-dispatch accounting: "launches" counts host->device dispatches
#: (a chained multi-segment scan is ONE launch — the whole plan runs
#: inside one jitted computation), "escalations" counts fast-tier
#: deaths that re-ran on the exact kernel. Tests assert on these to
#: pin the one-dispatch-per-plan and one-launch-per-key-batch
#: contracts; bench.py publishes them in engine_stats. Updates go
#: through _bump_launch: the dispatch plane's prep worker and
#: collecting callers launch concurrently, and unlocked += would drop
#: counts under the interleaving.
LAUNCH_STATS = {
    "launches": 0,
    "escalations": 0,
    # host_syncs: device->host fetches that pay a host round trip
    # (every fetch goes through _host_get). The residency contract is
    # host_syncs == 1 per segmented check, however many segments the
    # plan chains; bench publishes host_syncs/checks as syncs_per_check.
    "host_syncs": 0,
    # donated_buffers: chain launches whose input frontier buffer was
    # donated to the computation (resident backends only — see
    # sharded.residency_supported).
    "donated_buffers": 0,
}

_launch_stats_lock = threading.Lock()


def _bump_launch(key: str, n: int = 1) -> None:
    with _launch_stats_lock:
        LAUNCH_STATS[key] += n
    # flight-recorder mirror: one instant per bump, emitted AFTER the
    # stats lock drops (planelint JT302). Instant counts per name equal
    # the counter deltas exactly — the parity pin tests/test_obs.py
    # and the analyze --trace acceptance check rely on this.
    obs_trace.instant(key, kind="launch_stat", n=n)


def reset_launch_stats() -> None:
    with _launch_stats_lock:
        LAUNCH_STATS["launches"] = 0
        LAUNCH_STATS["escalations"] = 0
        LAUNCH_STATS["host_syncs"] = 0
        LAUNCH_STATS["donated_buffers"] = 0


def launch_stats_snapshot() -> dict:
    """Point-in-time copy of LAUNCH_STATS under its lock — the
    sanctioned aggregate read (planelint JT205): a bare
    dict(LAUNCH_STATS) can tear against a concurrent _bump_launch."""
    with _launch_stats_lock:
        return dict(LAUNCH_STATS)


def _host_get(x):
    """THE device->host fetch. Every sync that pays a host round trip
    funnels through here so LAUNCH_STATS["host_syncs"] counts
    exactly the sync-floor payments a check makes (one _host_get call =
    one sync, whatever pytree it pulls). Follow-up fetches of arrays
    the same computation already materialized (death artifacts, debug
    frontiers) use plain device_get/np.asarray — the floor was paid."""
    _bump_launch("host_syncs")
    with obs_trace.span("host_sync", kind="host_sync"):
        return jax.device_get(x)


def init_frontier(init_state, S: int, W: int) -> np.ndarray:
    """[S, M] fresh-scan frontier: the init-state row, empty mask.
    Built host-side (numpy): eager per-element device ops would pay a
    host round trip each."""
    M = bitset_words(W)
    fr = np.zeros((S, M), np.int32)
    fr[int(init_state) + 1, 0] = 1
    return fr


@functools.partial(
    jax.jit,
    static_argnames=("model_name", "S", "W", "interpret", "exact"),
)
def _bitset_scan(
    win, meta, fr_in, model_name, S, W, interpret=False, exact=True
):
    """Batched scan: win [n_keys, n*4*W] int8 FLAT (occ/f/a/b — int8
    on the wire to quarter the transfer, and 1-D per key because TPU
    tiled layouts pad the two minor dims to (32, 128): a [n, 4, W]
    int8 host array would inflate ~85x during the host-side relayout,
    which measured as >1 s of single-core repack for a 100k-op
    stream), meta [n_keys, n*META_COLS] int32 flat likewise, fr_in
    [n_keys, S, M] starting frontier -> (out [n_keys, 1, OUT_COLS],
    fr_out [n_keys, S, M] final frontier). The reshape to [n, 4, W] /
    [n, META_COLS] happens HERE, on device, where it's a cheap HBM
    relayout. Keys form the outer grid dimension — one launch, one
    host sync per batch; the frontier in/out pair lets segments with
    different W chain back-to-back on device (W12 -> W16 embeds the
    mask space as the first 128 words)."""
    n_keys = win.shape[0]
    n = win.shape[1] // (4 * W)
    B = step_block(W, interpret)
    assert n % B == 0, f"steps {n} not a multiple of {B}"
    kernel, M = _make_kernel(
        model_name, S, W, exact=exact, interpret=interpret
    )
    win = win.reshape(n_keys, n, 4, W).astype(jnp.int32)
    meta = meta.reshape(n_keys, n, META_COLS)
    return pl.pallas_call(
        kernel,
        grid=(n_keys, n // B),
        in_specs=[
            pl.BlockSpec(
                (1, B, 4, W),
                lambda k, i: (k, i, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (1, B, META_COLS),
                lambda k, i: (k, i, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((1, S, M), lambda k, i: (k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, OUT_COLS),
                lambda k, i: (k, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((1, S, M), lambda k, i: (k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_keys, 1, OUT_COLS), jnp.int32),
            jax.ShapeDtypeStruct((n_keys, S, M), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, M), jnp.int32),
            pltpu.VMEM((S, M), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(win, meta, fr_in)


def _chain_scan_impl(args, fr0, seg_ws, model_name, S, interpret, exact):
    """Whole-plan segment chain in ONE jitted computation -> one host
    dispatch. `args` is the flat (win0, meta0, win1, meta1, ...) tuple
    of packed device args, seg_ws the per-segment W buckets (static —
    each distinct plan shape compiles once). The frontier moves
    between mask spaces on device (_reshape_frontier: widening is a
    lane pad, narrowing a lane slice), so the W12-19 bucket chain pays
    zero host round-trips between buckets. Returns every segment's
    verdict row, final frontier, and input frontier (the input
    frontiers feed decode/debug paths; the exact re-run restarts from
    segment 0 regardless — see collect_steps_bitset_segmented)."""
    outs = []
    frs = []
    fr_ins = []
    fr = fr0
    for i, W in enumerate(seg_ws):
        fr = _reshape_frontier(fr, S, bitset_words(W))
        fr_ins.append(fr)
        out, fr = _bitset_scan(
            args[2 * i], args[2 * i + 1], fr,
            model_name=model_name, S=S, W=W, interpret=interpret,
            exact=exact,
        )
        outs.append(out)
        frs.append(fr)
    return tuple(outs), tuple(frs), tuple(fr_ins)


_CHAIN_STATIC = ("seg_ws", "model_name", "S", "interpret", "exact")

_chain_scan = functools.partial(
    jax.jit, static_argnames=_CHAIN_STATIC
)(_chain_scan_impl)

#: Resident twin: fr0 (positional arg 1) is DONATED, so the input
#: frontier's device buffer aliases the chain's frontier outputs in
#: place — between launches the frontier never allocates fresh HBM and
#: never visits the host. Callers hand over ownership: a donated fr0
#: must be freshly built per launch (every call site does). Only
#: dispatched when sharded.residency_supported() — XLA:CPU ignores
#: donation with a warning per call, and tier-1 must stay warning-clean.
_chain_scan_donated = functools.partial(
    jax.jit, static_argnames=_CHAIN_STATIC, donate_argnums=(1,)
)(_chain_scan_impl)


def _run_chain(args, fr0, seg_ws, model_name, S, interpret, exact):
    """Dispatch one whole-plan chain, picking the donating variant when
    the backend actually honors input-output aliasing."""
    from jepsen_tpu.checker.sharded import residency_supported

    if residency_supported():
        _bump_launch("donated_buffers")
        return _chain_scan_donated(
            args, fr0, seg_ws, model_name, S, interpret, exact
        )
    return _chain_scan(
        args, fr0, seg_ws, model_name, S, interpret, exact
    )


def pack_steps(steps: ReturnSteps):
    """Host-side packing: FLAT [n*4*W] int8 window scalars (occ/f/a/b
    — codes are < MAX_ROWS so int8 quarters the host->device upload, and
    flat because multi-dim int8 host arrays pay a ruinous tiled-layout
    repack on transfer; see _bitset_scan) + flat [n*META_COLS] int32
    per-step meta, padded to a STEP_BLOCK multiple."""
    B = STEP_BLOCK
    if len(steps) % B or not len(steps):
        steps = steps.padded(max(((len(steps) + B - 1) // B) * B, B))
    n = len(steps)
    meta = np.zeros((n, META_COLS), np.int32)
    meta[:, 0] = steps.slot
    meta[:, 1] = steps.live.astype(np.int32)
    meta[:, 2] = steps.op_index
    if steps.fresh is not None:
        meta[:, 3] = steps.fresh[:, 0]
    else:
        # No fresh tracking: treat every occupied slot as fresh (round
        # 0 becomes a full round — the pre-optimization behavior).
        bits = (1 << np.arange(steps.W, dtype=np.int64))[None, :]
        meta[:, 3] = (steps.occ * bits).sum(axis=1).astype(np.int32)
    win = np.stack(
        [steps.occ, steps.f, steps.a, steps.b], axis=1
    ).astype(np.int8)
    return win.reshape(-1), meta.reshape(-1)


def _out_to_verdicts(out: np.ndarray) -> List[Tuple[bool, bool, int]]:
    return [
        (bool(o[0]), bool(o[1]), int(o[2])) for o in out[:, 0, :]
    ]


def check_steps_bitset(
    steps: ReturnSteps,
    model: str = "cas-register",
    S: int = 8,
    interpret: bool = False,
    exact: bool = False,
) -> Tuple[bool, bool, int]:
    """Single-key check: (alive, taint, died_op_index). taint is the
    overflow analog in the verdict contract and is always False in
    practice (see module docstring).

    Two-tier: the fast fixed-round kernel decides alive verdicts
    (sound — its frontier is a subset of the true closure), and a
    fast-tier death re-runs on the exact while-loop kernel, whose
    verdicts are definite both ways. exact=True skips the fast tier.

    The packed device args memoize on the steps object (same discipline
    as wgl_pallas: ReturnSteps are treated as immutable once checked —
    every driver path builds them fresh via events_to_steps; mutating
    one in place after a check would replay stale device data)."""
    def pack_dev():
        win, meta = pack_steps(steps)
        return jnp.asarray(win[None]), jnp.asarray(meta[None])

    args = memo_on(steps, "_bitset_args", None, pack_dev)
    name = model if isinstance(model, str) else model.name
    fr0 = jnp.asarray(init_frontier(steps.init_state, S, steps.W)[None])

    def scan(exact_flag):
        _bump_launch("launches")
        return _bitset_scan(
            *args, fr0, model_name=name, S=S, W=steps.W,
            interpret=interpret, exact=exact_flag,
        )

    out, fr = scan(exact)
    verdict = _out_to_verdicts(_host_get(out))[0]
    if not verdict[0] and not exact:
        # fast-tier death is provisional (under-closure): exact decides
        _bump_launch("escalations")
        out, fr = scan(True)
        verdict = _out_to_verdicts(_host_get(out))[0]
    if not verdict[0]:
        # death artifact: the pre-filter frontier (decode_frontier)
        steps._death_frontier = np.asarray(fr)[0]
    return verdict


def _slice_steps(
    steps: ReturnSteps, start: int, end: int, W: int
) -> ReturnSteps:
    """Steps [start, end) with the window narrowed to W slots — valid
    only when none of them touches a slot >= W (split_point
    guarantees)."""
    return ReturnSteps(
        occ=steps.occ[start:end, :W],
        f=steps.f[start:end, :W],
        a=steps.a[start:end, :W],
        b=steps.b[start:end, :W],
        slot=steps.slot[start:end],
        live=steps.live[start:end],
        crashed=steps.crashed[start:end],
        op_index=steps.op_index[start:end],
        init_state=steps.init_state,
        W=W,
        fresh=(
            steps.fresh[start:end]
            if steps.fresh is not None
            else None
        ),
    )


def split_point(steps: ReturnSteps, W_low: int) -> int:
    """Number of leading steps whose windows fit W_low slots (the
    first step occupying or returning a slot >= W_low ends the run)."""
    if not len(steps):
        return 0
    touches = (
        np.any(steps.occ[:, W_low:], axis=1) | (steps.slot >= W_low)
    )
    hi = np.nonzero(touches)[0]
    return int(hi[0]) if len(hi) else len(steps)


@functools.partial(jax.jit, static_argnames=("S", "M_hi"))
def _embed_frontier(fr_lo, S, M_hi):
    """Device-side W_low -> W_high frontier embed: the low mask space
    IS the first M_lo words of the high one (masks with high bits
    clear are a lane prefix)."""
    pad = M_hi - fr_lo.shape[-1]
    return jnp.pad(fr_lo, ((0, 0), (0, 0), (0, pad)))


def _reshape_frontier(fr, S: int, M_to: int):
    """Move a [1, S, M] device frontier between mask spaces. Widening
    is a lane pad (_embed_frontier). NARROWING is a lane slice, legal
    exactly when every mask bit >= W_to is zero — guaranteed by the
    planner: a segment runs at W_to only when no slot >= W_to is
    occupied anywhere in it, and an unoccupied slot's mask bit is
    provably zero (a set bit means linearized-but-not-returned, which
    is an occupied slot)."""
    M_from = fr.shape[-1]
    if M_to > M_from:
        return _embed_frontier(fr, S, M_to)
    if M_to < M_from:
        return fr[:, :, :M_to]
    return fr


def required_buckets(steps: ReturnSteps) -> np.ndarray:
    """Per-step minimum W bucket: the smallest W_BUCKETS entry
    covering every occupied slot and the returning slot at that step
    (slots are 0-based, so slot k needs W >= k+1)."""
    n = len(steps)
    Wf = steps.occ.shape[1]
    occ = steps.occ.astype(bool)
    maxslot = np.where(
        occ.any(axis=1), Wf - 1 - np.argmax(occ[:, ::-1], axis=1), -1
    )
    need = np.maximum(maxslot, steps.slot) + 1
    wb = _w_buckets()
    wreq = np.full(n, wb[-1], np.int64)
    for b in reversed(wb):
        wreq[need <= b] = b
    return wreq


#: relative per-step cost of a segment at bucket W: a fixed machinery
#: term plus vector work proportional to the mask words (measured on
#: v5e: ~2 us machinery, ~0.2 us of round work per 128 words)
def _seg_cost(w: int) -> float:
    return 2.0 + 0.2 * (bitset_words(w) / MIN_WORDS)


def plan_segments(
    steps: ReturnSteps, min_len: int | None = None
) -> List[Tuple[int, int, int]]:
    """[(start, end, W)] segments over the WHOLE stream: each step
    runs at the narrowest bucket its window fits (per-op vector cost
    scales with 2^W), with short runs absorbed into a neighbor so
    every segment is worth its kernel launch. Unlike a
    leading-prefix-only split, narrow valleys AFTER the window has
    once widened still run narrow — the frontier legally narrows at
    the boundary because no occupied slot reaches the sliced-off
    lanes (see _reshape_frontier)."""
    n = len(steps)
    wb = _w_buckets()
    if n == 0 or steps.W <= wb[0]:
        return [(0, n, steps.W)]
    if min_len is None:
        # every launch costs host dispatch; bound the segment count
        min_len = max(512, n // 48)
    wreq = np.minimum(required_buckets(steps), steps.W)
    # Chunk-max planning (O(n) vectorized — the per-step requirement
    # flips thousands of times, so exact RLE merging is quadratic in
    # runs and measured >1 s on a 100k stream): fixed chunks take the
    # max requirement inside them, then equal neighbors coalesce. A
    # width spike widens only its own chunk.
    chunk = max(min_len // 2, STEP_BLOCK)
    n_chunks = (n + chunk - 1) // chunk
    padded = np.full(n_chunks * chunk, wb[0], wreq.dtype)
    padded[:n] = wreq
    cmax = padded.reshape(n_chunks, chunk).max(axis=1)
    runs: List[List[int]] = []
    for ci, v in enumerate(cmax):
        ln = min(chunk, n - ci * chunk)
        if runs and runs[-1][0] == int(v):
            runs[-1][1] += ln
        else:
            runs.append([int(v), ln])
    # absorb any still-short runs into their cheaper neighbor
    i = 0
    while len(runs) > 1 and i < len(runs):
        if runs[i][1] >= min_len:
            i += 1
            continue
        cands = []
        for j in (i - 1, i + 1):
            if 0 <= j < len(runs):
                vi, li = runs[i]
                vj, lj = runs[j]
                vm = max(vi, vj)
                added = li * (_seg_cost(vm) - _seg_cost(vi)) + lj * (
                    _seg_cost(vm) - _seg_cost(vj)
                )
                cands.append((added, j))
        _, j = min(cands)
        lo, hi = min(i, j), max(i, j)
        runs[lo] = [
            max(runs[lo][0], runs[hi][0]), runs[lo][1] + runs[hi][1]
        ]
        del runs[hi]
        i = max(lo - 1, 0)
    segs: List[Tuple[int, int, int]] = []
    start = 0
    for v, ln in runs:
        segs.append((start, start + ln, v))
        start += ln
    return segs


def _segment_args(steps: ReturnSteps, segs) -> tuple:
    """Flat (win0, meta0, win1, meta1, ...) packed device args for a
    plan, each segment memoized on the steps object (re-checks skip
    slicing/packing/upload entirely — the analyze seam's
    one-check-per-history pattern pays prep once)."""

    def packed(start, end, W):
        sub = _slice_steps(steps, start, end, W)
        sub = sub.padded(bucket(max(len(sub), 1), 64))
        win, meta = pack_steps(sub)
        return jnp.asarray(win[None]), jnp.asarray(meta[None])

    flat: List = []
    for start, end, W in segs:
        flat.extend(memo_on(
            steps, "_seg_args", (start, end, W),
            lambda s=start, e=end, w=W: packed(s, e, w),
        ))
    return tuple(flat)


def _plan_for(steps: ReturnSteps, min_len: int | None):
    """The memoized segment plan (keyed by min_len so explicit narrow
    plans in tests don't collide with the default)."""
    return memo_on(
        steps, "_seg_plan", min_len, lambda: plan_segments(steps, min_len)
    )


def launch_steps_bitset_segmented(
    steps: ReturnSteps,
    model: str = "cas-register",
    S: int = 8,
    interpret: bool = False,
    exact: bool = False,
    min_len: int | None = None,
    device=None,
):
    """Dispatch the multi-segment scan WITHOUT the final host fetch:
    the ENTIRE plan runs as one jitted computation (_chain_scan) — one
    host dispatch per plan, with every segment chained through the
    frontier in/out pair on device (widening is a lane pad, narrowing
    a lane slice — a narrow mask space is a lane prefix of the wide
    one). The returned handle carries each segment's device verdict +
    death frontier + input frontier for a later collect. By default
    segments run on the FAST fixed-round kernel; the collect escalates
    a death to the exact kernel.

    device: commit the packed args to a specific chip before the
    dispatch — jit follows committed data, so the dispatch plane's
    round-robin places independent chains on different devices and
    they execute concurrently (one compiled executable caches per
    placement). None keeps the default-device behavior byte-identical.
    """
    segs = _plan_for(steps, min_len)
    name = model if isinstance(model, str) else model.name
    args = _segment_args(steps, segs)
    fr0 = jnp.asarray(
        init_frontier(steps.init_state, S, segs[0][2])[None]
    )
    if device is not None:
        # planelint: disable=JT101 reason=args is a HOST tuple of device arrays; device_put re-commits each element without any device->host fetch
        args = tuple(jax.device_put(a, device) for a in args)
        fr0 = jax.device_put(fr0, device)
    seg_ws = tuple(W for _, _, W in segs)
    _bump_launch("launches")
    outs, frs, fr_ins = _run_chain(
        args, fr0, seg_ws, name, S, interpret, exact
    )
    return list(outs), list(frs), (
        segs, list(fr_ins), name, S, interpret, exact
    )


def collect_steps_bitset_segmented(
    steps: ReturnSteps, handle, outs_host=None
) -> Tuple[bool, bool, int]:
    """Block on a launch_steps_bitset_segmented handle: one device_get
    for every segment's verdict; the first death wins. A death on the
    fast tier is provisional (its under-closed frontier is a subset of
    the true one — see _make_kernel), so the plan re-runs on the exact
    kernel — restarted from SEGMENT 0 with a fresh init frontier, not
    from the dying segment's input frontier: closure is skipped at
    steps with no fresh invokes, so under-closure introduced before a
    segment boundary is never repaired downstream, and any fast-tier
    frontier (fr_ins[k] included) may silently miss configs. Only a
    from-scratch exact pass makes the invalid verdict definite.

    outs_host: the already-fetched host copies of the handle's out
    arrays — the dispatch plane fetches a whole launch train in one
    device_get and hands each launch its slice, skipping the per-plan
    sync here."""
    outs, frs, (segs, fr_ins, name, S, interpret, exact) = handle
    fetched = (
        _host_get(tuple(outs)) if outs_host is None else outs_host
    )
    taint = False
    for k, (o, dead_fr) in enumerate(zip(fetched, frs)):
        alive, t, died = _out_to_verdicts(np.asarray(o))[0]
        taint = taint or t
        if not alive:
            if exact:
                steps._death_frontier = np.asarray(dead_fr)[0]
                return False, taint, died
            _bump_launch("launches")
            _bump_launch("escalations")
            args = _segment_args(steps, segs)  # memo hit: packed above
            fr0 = jnp.asarray(
                init_frontier(steps.init_state, S, segs[0][2])[None]
            )
            seg_ws = tuple(W for _, _, W in segs)
            # Collect-time exact re-run: outside the plane's launch
            # guard, so it runs through its own chaos seam (transient
            # faults retry; exhaustion raises PlaneFault upward).
            from jepsen_tpu.checker import chaos

            with obs_trace.span("launch", kind="launch", exact=True):
                outs2, frs2, _ = chaos.resilient_call(
                    lambda: _run_chain(
                        args, fr0, seg_ws, name, S, interpret, True
                    ),
                    site="launch",
                )
            # planelint: disable=JT101 reason=the exact escalation re-run syncs ONCE (batched tuple fetch); the enclosing loop always exits via return after it
            for o2, f2 in zip(_host_get(tuple(outs2)), frs2):
                alive2, t2, died2 = _out_to_verdicts(np.asarray(o2))[0]
                taint = taint or t2
                if not alive2:
                    steps._death_frontier = np.asarray(f2)[0]
                    return False, taint, died2
            return True, taint, -1
    return True, taint, -1


def check_steps_bitset_segmented_checkpointed(
    steps: ReturnSteps,
    sink,
    model: str = "cas-register",
    S: int = 8,
    interpret: bool = False,
    min_len: int | None = None,
) -> Tuple[bool, bool, int]:
    """Durable RESIDENT variant of the segmented scan: segments chain
    on device in boundary groups — every `sink.every` segments form ONE
    launch (`_run_chain`, frontier donated on resident backends), and
    the frontier only visits the host at the persistence boundary that
    ends the group, where it checkpoints atomically before the next
    group starts. With every=1 (the default) that degenerates to one
    launch + one durable boundary per segment — the maximally
    crash-granular schedule; with every >= len(plan) the whole durable
    check pays ONE host sync, same as the plain segmented path. A
    killed process re-enters at the last durable frontier and re-runs
    only unverified groups; a finished checkpoint replays its verdict
    with ZERO launches.

    Soundness: a fast-tier boundary frontier equals the uninterrupted
    chain's (same kernels, same inputs), and fast ALIVE verdicts are
    definite — so fast boundaries are safe resume points. A fast-tier
    DEATH is provisional: the sink invalidates back to segment 0
    (restart-from-segment-0 semantics, durably recording the
    escalation) and the exact pass checkpoints its own, fully-closed
    frontiers. Stale or tampered checkpoints (content hash mismatch)
    are rejected in sink.begin() and the check runs cold."""
    from jepsen_tpu.checker import chaos
    from jepsen_tpu.checker import checkpoint as _cp

    min_len = min_len if min_len is not None else sink.seg_min_len
    segs = _plan_for(steps, min_len)
    name = model if isinstance(model, str) else model.name
    chash = _cp.steps_content_hash(steps, name, S, segs)
    state = sink.begin(chash, segs, name, S)
    v = state.get("verdict")
    if v is not None:
        # Finished checkpoint: replay, zero launches.
        fr = sink.death_frontier_array()
        if fr is not None:
            steps._death_frontier = fr
        return bool(v["alive"]), bool(v["taint"]), int(v["died"])
    exact = bool(state.get("exact", False))
    start = int(state.get("segments_done", 0))
    fr_host = sink.frontier_array()
    taint = False
    group_n = max(int(getattr(sink, "every", 1)), 1)
    while True:  # one iteration per tier; escalation restarts the loop
        if start == 0 or fr_host is None:
            start = 0
            fr_host = init_frontier(steps.init_state, S, segs[0][2])[None]
        k = start
        escalated = False
        while k < len(segs):
            g = min(k + group_n, len(segs))
            group = segs[k:g]
            args = _segment_args(steps, group)
            fr0 = jnp.asarray(fr_host)
            seg_ws = tuple(W for _, _, W in group)
            _bump_launch("launches")
            run_exact = exact

            def one_group(a=args, f=fr0, ws=seg_ws, ex=run_exact):
                outs, frs, _ = _run_chain(
                    a, f, ws, name, S, interpret, ex
                )
                # ONE host sync per durable boundary: every group
                # verdict row + the boundary frontier in a single
                # fetch; the per-segment frontiers stay on device
                # (only a terminal death pulls one more, below).
                o_h, fr_h = _host_get((tuple(outs), frs[-1]))
                return o_h, fr_h, frs
            # Same chaos seam as the plain collect path: transient
            # faults retry, exhaustion raises PlaneFault upward.
            o_host, fr_last, frs = chaos.resilient_call(
                one_group, site="launch"
            )
            died_seg, died = -1, -1
            for gi, o in enumerate(o_host):
                alive, t, d = _out_to_verdicts(np.asarray(o))[0]
                taint = taint or t
                if not alive:
                    died_seg, died = gi, d
                    break  # first death wins; downstream is garbage
            if died_seg >= 0:
                if not exact:
                    # Provisional fast death: every fast checkpoint is
                    # void — durably escalate, restart from segment 0.
                    _bump_launch("escalations")
                    exact = True
                    sink.invalidate(reason="exact-escalation")
                    fr_host = None
                    escalated = True
                    break
                # planelint: disable=JT104 reason=post-death artifact fetch; the group's counted _host_get already paid and guarded the crossing
                death_fr = np.asarray(jax.device_get(frs[died_seg]))[0]
                steps._death_frontier = death_fr
                sink.finish(
                    alive=False, taint=taint, died=died,
                    death_frontier=death_fr,
                )
                return False, taint, died
            fr_host = np.asarray(fr_last)
            k = g
            sink.record(segments_done=k, frontier=fr_host, exact=exact)
        if escalated:
            start = 0
            continue
        sink.finish(alive=True, taint=taint, died=-1)
        return True, taint, -1


def check_steps_bitset_segmented(
    steps: ReturnSteps,
    model: str = "cas-register",
    S: int = 8,
    interpret: bool = False,
    min_len: int | None = None,
    checkpoint=None,
) -> Tuple[bool, bool, int]:
    """Multi-segment scan for crash-accumulating histories: the prefix
    runs on the narrowest kernel its windows fit (per-op cost scales
    16x per bucket), widening as crashed slots pile up, all segments
    chained through the frontier in/out pair with NO host sync in
    between — ONE dispatch for the whole plan. The host fetches every
    segment's verdict in one device_get; the first death wins.

    checkpoint: a checkpoint.CheckpointSink switches to the durable
    boundary-group driver (one launch and one host sync per `every`-
    segment persistence group, so every=len(plan) matches this path's
    single sync — see check_steps_bitset_segmented_checkpointed)."""
    if checkpoint is not None:
        return check_steps_bitset_segmented_checkpointed(
            steps, checkpoint, model=model, S=S, interpret=interpret,
            min_len=min_len,
        )
    segs = _plan_for(steps, min_len)
    if len(segs) == 1:
        # Not worth multiple launches: one scan, shape-bucketed. The
        # padded object memoizes on steps so re-checks reuse its
        # packed device args.
        padded = memo_on(
            steps, "_padded_single", None,
            lambda: steps.padded(bucket(max(len(steps), 1), 64)),
        )
        verdict = check_steps_bitset(
            padded, model=model, S=S, interpret=interpret
        )
        fr = getattr(padded, "_death_frontier", None)
        if fr is not None:
            steps._death_frontier = fr
        return verdict
    return collect_steps_bitset_segmented(
        steps,
        launch_steps_bitset_segmented(
            steps, model=model, S=S, interpret=interpret,
            min_len=min_len,
        ),
    )


def decode_frontier(
    fr: np.ndarray,
    steps: ReturnSteps,
    died_op_index: int,
    model,
    decode_value=None,
    max_configs: int = 10,
) -> dict:
    """Decode a death's pre-filter frontier into the reference-style
    failure report (checker.clj:146-158, truncated to 10 configs):
    the returning op that could not linearize, and each surviving
    config's state + which open ops it had/hadn't linearized."""
    from jepsen_tpu.checker.models import model as get_model

    m = get_model(model)
    f_names: dict = {}
    for name, code in m.f_names.items():
        f_names.setdefault(code, str(name))
    dec = decode_value or (lambda c: c)

    rows = np.nonzero(steps.op_index == died_op_index)[0]
    if not len(rows):
        return {"configs": [], "note": "death step not found"}
    i = int(rows[0])
    W = steps.W

    def op_desc(slot: int) -> dict:
        d = {
            "slot": slot,
            "f": f_names.get(int(steps.f[i, slot]), "?"),
            "value": dec(int(steps.a[i, slot])),
        }
        if d["f"] in ("cas", "compare-and-set"):
            d["value"] = [
                dec(int(steps.a[i, slot])), dec(int(steps.b[i, slot]))
            ]
        return d

    configs = []
    S, M = fr.shape
    for s in range(S):
        if len(configs) >= max_configs:
            break
        words = np.nonzero(fr[s])[0]
        for w in words:
            word = int(fr[s, w])
            for b in range(32):
                if not (word >> b) & 1:
                    continue
                mask = int(w) * 32 + b
                linearized = [
                    op_desc(j) for j in range(W)
                    if (mask >> j) & 1 and steps.occ[i, j]
                ]
                pending = [
                    op_desc(j) for j in range(W)
                    if not (mask >> j) & 1 and steps.occ[i, j]
                ]
                configs.append({
                    "state": dec(s - 1) if s > 0 else None,
                    "linearized": linearized,
                    "pending": pending,
                })
                if len(configs) >= max_configs:
                    break
            if len(configs) >= max_configs:
                break
    return {
        "failed_op": op_desc(int(steps.slot[i])),
        "configs": configs,
    }


def launch_keys_bitset(
    steps_list,
    model: str = "cas-register",
    S: int = 8,
    interpret: bool = False,
    exact: bool = False,
    mesh=None,
):
    """Dispatch the batched per-key scan WITHOUT a host sync: returns
    a handle with the device verdict array. Collecting later
    (collect_keys_bitset) lets callers pipeline several batches'
    device work behind one another — the host round-trip floor is
    paid once per pipeline, not once per batch. Keys run on the fast
    fixed-round kernel by default; the collect re-checks any key the
    fast tier reported dead on the exact kernel (see _make_kernel).

    mesh (a jax.sharding.Mesh of >1 device): the key axis pads to a
    multiple of the mesh size with blank rows (no live steps —
    trivially alive, sliced off at collect) and the batch dispatches
    through the shard_map wrapper (sharded.make_sharded_bitset):
    B keys run B/n_devices per chip, still ONE launch and one sync.
    mesh=None (or a 1-device mesh) keeps the single-device dispatch
    byte-identical."""
    n = bucket(max(max(len(st) for st in steps_list), 1), 64)
    name = model if isinstance(model, str) else model.name
    W = steps_list[0].W
    wins, metas = [], []
    for st in steps_list:
        # per-key packing memoizes like _seg_args (keyed by the batch
        # pad length): re-checking the same streams repacks nothing
        w, m = memo_on(
            st, "_batch_args", n, lambda s=st: pack_steps(s.padded(n))
        )
        wins.append(w)
        metas.append(m)
    n_real = len(steps_list)
    win_h = np.stack(wins)
    meta_h = np.stack(metas)
    fr0_h = np.stack([
        init_frontier(st.init_state, S, W) for st in steps_list
    ])
    n_dev = 0
    if mesh is not None:
        from jepsen_tpu.checker.sharded import mesh_size

        n_dev = mesh_size(mesh)
    if n_dev > 1:
        from jepsen_tpu.checker.sharded import (
            make_sharded_bitset,
            note_sharded_launch,
        )
        from jepsen_tpu.pod.slicing import host_shard_put

        pad = -n_real % n_dev
        if pad:
            win_h = np.concatenate([
                win_h,
                np.zeros((pad,) + win_h.shape[1:], win_h.dtype),
            ])
            meta_h = np.concatenate([
                meta_h,
                np.zeros((pad,) + meta_h.shape[1:], meta_h.dtype),
            ])
            fr0_h = np.concatenate([
                fr0_h,
                np.repeat(init_frontier(0, S, W)[None], pad, axis=0),
            ])
        # key-spec placement; in a pod each process materializes only
        # its addressable host-local shards (pod.slicing).
        win_j, meta_j, fr0 = host_shard_put(
            (win_h, meta_h, fr0_h), mesh
        )
        fn = make_sharded_bitset(mesh, name, S, W, interpret, exact)
        _bump_launch("launches")
        note_sharded_launch(n_dev)
        out, _ = fn(win_j, meta_j, fr0)
    else:
        mesh = None  # a 1-device mesh IS the single-device path
        win_j = jnp.asarray(win_h)
        meta_j = jnp.asarray(meta_h)
        fr0 = jnp.asarray(fr0_h)
        _bump_launch("launches")
        out, _ = _bitset_scan(
            win_j, meta_j, fr0,
            model_name=name,
            S=S,
            W=W,
            interpret=interpret,
            exact=exact,
        )
    return out, (
        win_j, meta_j, fr0, name, S, W, interpret, exact, mesh, n_real
    )


def collect_keys_bitset(handle, out_host=None) -> List[Tuple[bool, bool, int]]:
    """Block on a launch_keys_bitset handle and decode verdicts,
    re-running the whole batch on the exact kernel if any key's fast
    verdict was a (provisional) death. A sharded launch escalates
    sharded too (its device args are already mesh-resident); padding
    rows are sliced off before the verdicts return.

    out_host: pre-fetched host copy of the handle's out array (the
    dispatch plane's one-sync-per-train collect); the escalation
    re-run, when needed, still syncs on its own."""
    out, (
        win_j, meta_j, fr0, name, S, W, interpret, exact, mesh, n_real
    ) = handle
    if out_host is None and mesh is not None:
        # pod collect: the sharded verdict array is not fully
        # addressable across processes — one replicating all-gather
        # (no-op single-process) before the funnel.
        from jepsen_tpu.pod.slicing import global_view

        out = global_view((out,), mesh)[0]
    verdicts = _out_to_verdicts(
        np.asarray(_host_get(out) if out_host is None else out_host)
    )[:n_real]
    if exact or all(v[0] for v in verdicts):
        return verdicts
    # A fast-tier death is provisional: the exact kernel decides. The
    # whole batch re-runs in one launch (device args are already
    # resident; dead keys are rare, so this is the uncommon path).
    # The re-run happens at COLLECT time, outside the dispatch plane's
    # launch guard, so it carries its own chaos seam: transient faults
    # retry here; an exhausted budget raises PlaneFault for the
    # plane's degradation ladder (or the sequential caller) to absorb.
    from jepsen_tpu.checker import chaos

    _bump_launch("launches")
    _bump_launch("escalations")
    if mesh is not None:
        from jepsen_tpu.checker.sharded import (
            make_sharded_bitset,
            mesh_size,
            note_sharded_launch,
        )

        fn = make_sharded_bitset(mesh, name, S, W, interpret, True)
        note_sharded_launch(mesh_size(mesh))
        out2, _ = chaos.resilient_call(
            lambda: fn(win_j, meta_j, fr0), site="launch",
            devices=[str(d) for d in mesh.devices.flat],
        )
        from jepsen_tpu.pod.slicing import global_view

        out2 = global_view((out2,), mesh)[0]
    else:
        out2, _ = chaos.resilient_call(
            lambda: _bitset_scan(
                win_j, meta_j, fr0,
                model_name=name, S=S, W=W, interpret=interpret,
                exact=True,
            ),
            site="launch",
        )
    return _out_to_verdicts(np.asarray(_host_get(out2)))[:n_real]


def launch_tails_bitset(
    steps_list,
    frontiers,
    model: str = "cas-register",
    S: int = 8,
    interpret: bool = False,
    exact: bool = False,
    mesh=None,
):
    """Dispatch a stack of stream TAILS in one launch: like
    launch_keys_bitset, but row i chains from stream i's OWN boundary
    frontier (``frontiers[i]``: a device-resident [S, M] row from a
    previous stacked launch, a host [S, M] / [1, S, M] array, or None
    for a fresh stream = init_frontier) instead of a cold init row —
    and the handle KEEPS the stacked fr_out, so each stream's next
    frontier is a device-side row slice, never a host sync.

    All tails must share (model, S, W); lengths pad to one power-of-two
    bucket (the dispatch plane's "stream" bucket key guarantees both).
    mesh (>1 device): rows pad to a mesh multiple with blank init rows
    and the stack dispatches through the shard_map wrapper with
    matched in/out key shardings — the same one-launch-one-sync shape
    as batch buckets (single-process meshes; pod streams are not
    routed here). Returns (out, handle); slice ``handle[0][i]`` for
    stream i's boundary frontier after collecting ``out``."""
    n = bucket(max(max(len(st) for st in steps_list), 1), 64)
    name = model if isinstance(model, str) else model.name
    W = steps_list[0].W
    M = bitset_words(W)
    wins, metas = [], []
    for st in steps_list:
        w, m = memo_on(
            st, "_batch_args", n, lambda s=st: pack_steps(s.padded(n))
        )
        wins.append(w)
        metas.append(m)
    n_real = len(steps_list)
    win_h = np.stack(wins)
    meta_h = np.stack(metas)
    n_dev = 0
    if mesh is not None:
        from jepsen_tpu.checker.sharded import mesh_size

        n_dev = mesh_size(mesh)
    # Frontier rows may live on different devices (each is a slice of
    # an earlier stacked launch's sharded fr_out): normalize every row
    # onto one device before stacking — a no-op when already there —
    # so jnp.stack never sees conflicting committed placements.
    dev0 = (
        list(mesh.devices.flat)[0] if n_dev > 1 else jax.devices()[0]
    )
    rows = []
    for st, fr in zip(steps_list, frontiers):
        if fr is None:
            fr = init_frontier(st.init_state, S, W)
        r = jnp.asarray(fr).reshape(S, M)
        rows.append(jax.device_put(r, dev0))
    if n_dev > 1:
        from jax.sharding import NamedSharding

        from jepsen_tpu.checker.sharded import (
            key_spec,
            make_sharded_bitset,
            note_sharded_launch,
        )

        pad = -n_real % n_dev
        if pad:
            win_h = np.concatenate([
                win_h,
                np.zeros((pad,) + win_h.shape[1:], win_h.dtype),
            ])
            meta_h = np.concatenate([
                meta_h,
                np.zeros((pad,) + meta_h.shape[1:], meta_h.dtype),
            ])
            blank = jnp.asarray(init_frontier(0, S, W))
            rows.extend([jax.device_put(blank, dev0)] * pad)
        sharding = NamedSharding(mesh, key_spec(mesh))
        win_j = jax.device_put(jnp.asarray(win_h), sharding)
        meta_j = jax.device_put(jnp.asarray(meta_h), sharding)
        fr0 = jax.device_put(jnp.stack(rows), sharding)
        fn = make_sharded_bitset(mesh, name, S, W, interpret, exact)
        _bump_launch("launches")
        note_sharded_launch(n_dev)
        out, fr_out = fn(win_j, meta_j, fr0)
    else:
        mesh = None  # a 1-device mesh IS the single-device path
        win_j = jnp.asarray(win_h)
        meta_j = jnp.asarray(meta_h)
        fr0 = jnp.stack(rows)
        _bump_launch("launches")
        out, fr_out = _bitset_scan(
            win_j, meta_j, fr0,
            model_name=name,
            S=S,
            W=W,
            interpret=interpret,
            exact=exact,
        )
    return out, (fr_out, name, S, W, interpret, exact, mesh, n_real)


def check_keys_bitset(
    steps_list,
    model: str = "cas-register",
    S: int = 8,
    interpret: bool = False,
    exact: bool = False,
    mesh=None,
) -> List[Tuple[bool, bool, int]]:
    """Batch of per-key checks in ONE kernel launch + host sync (two
    launches when a fast-tier death escalates to the exact kernel).
    All steps must share W; lengths pad to a power-of-two bucket so one
    compiled kernel serves every batch.

    Routed through the process-wide dispatch plane (checker.dispatch):
    the batch is still exactly one launch (the launch-count contracts
    above hold unchanged), but it joins the plane's launch train and
    stats surface, so concurrent callers pipeline behind one another
    and collect with a shared sync.

    mesh: None lets the plane decide (its own mesh — all visible
    devices when >1), False forces the single-device dispatch, a Mesh
    shards the batch explicitly."""
    from jepsen_tpu.checker.dispatch import default_plane

    return default_plane().run_keys(
        steps_list, model=model, S=S, interpret=interpret, exact=exact,
        mesh=mesh,
    )
