"""Pallas TPU megakernel for the WGL linearizability scan.

The pure-JAX kernel (wgl_jax.py) is dispatch-bound: a sequential
`lax.scan` pays ~2µs of device overhead per primitive op, and a WGL
return-step needs dozens of them, so single-key checking tops out
~100-400µs/step no matter how small the tensors are. This module
compiles the ENTIRE scan into one Pallas kernel: the frontier lives in
VMEM scratch across a sequential grid (one grid step per RETURN), flags
live in SMEM, and each step's closure runs as a handful of VPU tile
ops — per-step cost drops to the single-digit microseconds the actual
compute requires.

Same algorithm and exactly the same semantics as wgl_jax.py (see its
module docstring for the formulation, dominance pruning, and the
soundness-under-overflow argument), with these restrictions:

- single mask word: window W <= 32 (wider windows route to the
  pure-JAX path via the escalation ladder in linearizable.py);
- K frontier slots (static, default 128).

TPU shape discipline inside the kernel:
- the frontier is [1, K] int32 rows (K lanes); per-step window data
  arrives as [1, W] rows and is moved into [W, 1] columns with an
  identity-mask reduction (`_col`) — Mosaic-friendly, no transposes;
- candidates are [W, K] tiles; dedup-vs-table and slot assignment are
  [W, K, K] broadcast compares; the frontier self-prune is [K, K];
- cumulative sums use static shift-and-add doubling (concat+slice), no
  cumsum primitive required.

Reference role: the knossos search behind
jepsen/src/jepsen/checker.clj:127-158 — here as a single fused
accelerator kernel instead of a JVM graph search.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jepsen_tpu.checker.events import ReturnSteps, slot_bit_table
from jepsen_tpu.checker.models import model as get_model

#: meta columns: slotbit, live, crashed, op_index, init_state
META_COLS = 8

#: return-steps per grid iteration: amortizes the per-iteration block
#: DMA overhead (the dominant cost for tiny [1, W] blocks) across B
#: steps; the kernel loops over the B sub-steps internally.
STEP_BLOCK = 8


def _cumsum_excl(x, axis, size):
    """Exclusive prefix sum along `axis` via static shift-and-add
    doubling. Lane-axis shifts use pltpu.roll (a rotate the VPU does in
    one op — the concat+slice alternative forced Mosaic into a
    pathological lowering, ~100x slower per round); the sublane axis
    uses concat+slice, which lowers fine there."""
    incl = x
    sh = 1
    if axis == 1 and hasattr(pltpu, "roll"):
        lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
        while sh < size:
            rolled = pltpu.roll(incl, sh, 1)
            incl = incl + jnp.where(lane >= sh, rolled, 0)
            sh *= 2
        return incl - x
    while sh < size:
        zshape = list(x.shape)
        zshape[axis] = sh
        z = jnp.zeros(zshape, x.dtype)
        if axis == 0:
            shifted = jnp.concatenate([z, incl[: size - sh, :]], axis=0)
        else:
            shifted = jnp.concatenate([z, incl[:, : size - sh]], axis=1)
        incl = incl + shifted
        sh *= 2
    return incl - x


def _make_kernel(model_name: str, K: int, W: int):
    step_jax = get_model(model_name).step_jax

    B = STEP_BLOCK

    def kernel(win_ref, meta_ref, out_ref, fs_ref, fm_ref, fv_ref):
        # Grid: (keys, step-blocks). Steps iterate fastest, so the
        # per-key scratch frontier resets at each key's first block.
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            lane = lax.broadcasted_iota(jnp.int32, (1, K), 1)
            init_state = meta_ref[0, 0, 0, 4]
            fs_ref[:] = jnp.where(lane == 0, init_state, 0)
            fm_ref[:] = jnp.zeros((1, K), jnp.int32)
            fv_ref[:] = (lane == 0).astype(jnp.int32)
            out_ref[0, 0, 0] = 1  # alive
            out_ref[0, 0, 1] = 0  # overflow
            out_ref[0, 0, 2] = -1  # died op index
            out_ref[0, 0, 3] = 0  # reserved
            out_ref[0, 0, 4] = 0  # reserved
            out_ref[0, 0, 5] = 0  # total closure rounds (debug)
            out_ref[0, 0, 6] = 0  # max closure rounds in one step (debug)
            out_ref[0, 0, 7] = -1  # first tainted step (debug)

        for b in range(B):
            _substep(win_ref, meta_ref, out_ref, fs_ref, fm_ref, fv_ref,
                     i * B + b, b)

    def _substep(win_ref, meta_ref, out_ref, fs_ref, fm_ref, fv_ref, gi, b):
        slotbit = meta_ref[0, b, 0, 0]
        live = meta_ref[0, b, 0, 1]
        crashed = meta_ref[0, b, 0, 2]
        opidx = meta_ref[0, b, 0, 3]
        alive = out_ref[0, 0, 0]

        @pl.when((alive == 1) & (live == 1))
        def _step():
            # Layout discipline (the difference between ~3us and ~30us
            # per step): lane-axis reductions of 3-D tensors are slow in
            # Mosaic, so every [K, ...] reduction here runs over the
            # LEADING axis, and [1, K] <-> [K, 1] moves use the native
            # 32-bit sublane/lane transpose (jnp.swapaxes).
            occ_c = jnp.swapaxes(win_ref[0, b, 0:1, :], 0, 1)  # [W, 1]
            sf_c = jnp.swapaxes(win_ref[0, b, 1:2, :], 0, 1)
            sa_c = jnp.swapaxes(win_ref[0, b, 2:3, :], 0, 1)
            sb_c = jnp.swapaxes(win_ref[0, b, 3:4, :], 0, 1)
            bit_w = jnp.left_shift(
                jnp.int32(1), lax.broadcasted_iota(jnp.int32, (W, 1), 0)
            )

            ii = lax.broadcasted_iota(jnp.int32, (K, K), 0)
            jj = lax.broadcasted_iota(jnp.int32, (K, K), 1)

            def prune(fs, fm, fv):
                """Frontier self-canonicalize: kill exact duplicates
                (lowest lane wins) and dominated configs ([K, K],
                reduced over sublanes)."""
                fs_c = jnp.swapaxes(fs, 0, 1)  # [K, 1]
                fm_c = jnp.swapaxes(fm, 0, 1)
                fv_c = jnp.swapaxes(fv, 0, 1)
                eq_s = fs_c == fs
                m_eq = fm_c == fm
                live_eq = (fm_c & ~crashed) == (fm & ~crashed)
                cra_i = fm_c & crashed
                cra_sub = (cra_i & (fm & crashed)) == cra_i
                dup = eq_s & m_eq & (ii < jj)
                dom = eq_s & live_eq & cra_sub & ~m_eq
                both = (fv_c == 1) & (fv == 1)
                kill = jnp.any(both & (dup | dom), axis=0, keepdims=True)
                fv2 = fv * (1 - kill.astype(jnp.int32))
                return fv2, jnp.sum(kill.astype(jnp.int32) * fv) > 0

            def round_fn(st):
                fs, fm, fv, go, ovf, r = st
                # Expand: [W, K] candidates.
                lin = (fm & bit_w) != 0
                ok, s2 = step_jax(fs, sf_c, sa_c, sb_c)
                cv = (fv == 1) & (occ_c == 1) & ~lin & ok
                cm = fm | bit_w
                cs = jnp.broadcast_to(s2, (W, K))
                cmb = jnp.broadcast_to(cm, (W, K))
                # Dedup + dominance-filter vs table: [K_t, W, K_c],
                # reduced over the leading (table) axis. Filtering
                # candidates the table already dominates BEFORE insertion
                # keeps doomed configs from flooding the free slots (and
                # from inflating the capacity-overflow test) — this is
                # what makes the table's effective capacity the
                # post-prune width, like the pure-JAX canonicalize.
                fs_c3 = jnp.swapaxes(fs, 0, 1)[:, :, None]  # [K, 1, 1]
                fm_c3 = jnp.swapaxes(fm, 0, 1)[:, :, None]
                fv_c3 = jnp.swapaxes(fv, 0, 1)[:, :, None]
                same_s = (fs_c3 == cs[None, :, :]) & (fv_c3 == 1)
                eq3 = same_s & (fm_c3 == cmb[None, :, :])
                cra_t = fm_c3 & crashed
                dom3 = (
                    same_s
                    & ((fm_c3 & ~crashed) == (cmb[None, :, :] & ~crashed))
                    & ((cra_t & cmb[None, :, :]) == cra_t)
                    & (fm_c3 != cmb[None, :, :])
                )
                new = (cv & ~jnp.any(eq3 | dom3, axis=0)).astype(jnp.int32)
                # Flattened exclusive rank of each new candidate.
                lane_x = _cumsum_excl(new, axis=1, size=K)
                row_tot = jnp.sum(new, axis=1, keepdims=True)
                row_off = _cumsum_excl(row_tot, axis=0, size=W)
                rank = lane_x + row_off
                # Free-slot exclusive rank.
                free = 1 - fv
                frank = _cumsum_excl(free, axis=1, size=K)
                nfree = jnp.sum(free)
                # Assignment: candidate with rank r -> r-th free slot.
                A = (
                    (new[:, :, None] == 1)
                    & (free.reshape(1, 1, K) == 1)
                    & (rank[:, :, None] == frank.reshape(1, 1, K))
                ).astype(jnp.int32)
                ins = jnp.sum(A, axis=(0, 1)).reshape(1, K)
                fs2 = jnp.where(
                    ins == 1,
                    jnp.sum(A * cs[:, :, None], axis=(0, 1)).reshape(1, K),
                    fs,
                )
                fm2 = jnp.where(
                    ins == 1,
                    jnp.sum(A * cmb[:, :, None], axis=(0, 1)).reshape(1, K),
                    fm,
                )
                fv2 = jnp.maximum(fv, ins)
                n_ins = jnp.sum(ins)
                fv3, _ = prune(fs2, fm2, fv2)
                # Array fixpoint: every round is a deterministic function
                # of the table array, so set-stability implies
                # array-stability after at most one extra round — even
                # through the insert/prune oscillation where dominated
                # configs are regenerated each round by their persistent
                # sources. Capacity-with-retry: candidates that found no
                # free slot are regenerated next round; only a round that
                # drops candidates while changing NOTHING is a genuine
                # capacity overflow.
                changed = (
                    jnp.any(fs2 != fs)
                    | jnp.any(fm2 != fm)
                    | jnp.any(fv3 != fv)
                )
                leftover = jnp.sum(new) > n_ins
                return (fs2, fm2, fv3, changed,
                        ovf | (leftover & ~changed), r + 1)

            def cond_fn(st):
                _, _, _, go, _, r = st
                return go & (r <= 2 * W + 8)

            init = (
                fs_ref[:], fm_ref[:], fv_ref[:],
                jnp.bool_(True), jnp.bool_(False), jnp.int32(0),
            )
            fs, fm, fv, go, ovf, nr = lax.while_loop(cond_fn, round_fn, init)
            out_ref[0, 0, 5] = out_ref[0, 0, 5] + nr
            out_ref[0, 0, 6] = jnp.maximum(out_ref[0, 0, 6], nr)
            # go still set => round bound hit without convergence: taint.
            ovf = ovf | go

            # Filter: keep configs with the returning op linearized,
            # clear its bit (no merge possible — wgl_jax docstring).
            has = ((fm & slotbit) != 0).astype(jnp.int32)
            fv = fv * has
            fm = fm & ~slotbit
            fs_ref[:] = fs
            fm_ref[:] = fm
            fv_ref[:] = fv

            any_live = jnp.sum(fv) > 0

            @pl.when(jnp.logical_not(any_live))
            def _died():
                out_ref[0, 0, 0] = 0
                out_ref[0, 0, 2] = opidx

            @pl.when(ovf & (out_ref[0, 0, 1] == 0))
            def _ovf_first():
                out_ref[0, 0, 7] = gi  # first tainted step (debug)

            @pl.when(ovf)
            def _ovf():
                out_ref[0, 0, 1] = 1

    return kernel


@functools.partial(
    jax.jit, static_argnames=("model_name", "K", "W", "interpret")
)
def _pallas_scan(win, meta, model_name, K, W, interpret=False):
    """Batched scan: win [n_keys, n, 4, W], meta
    [n_keys, n, 1, META_COLS] -> out [n_keys, META_COLS]. Keys form the
    outer grid dimension (independent scans, one kernel launch, ONE
    host sync for the whole batch — the multi-key analysis plane)."""
    n_keys, n = win.shape[0], win.shape[1]
    B = STEP_BLOCK
    assert n % B == 0, f"steps {n} not a multiple of {B}"
    kernel = _make_kernel(model_name, K, W)
    out = pl.pallas_call(
        kernel,
        grid=(n_keys, n // B),
        in_specs=[
            pl.BlockSpec((1, B, 4, W), lambda k, i: (k, i, 0, 0)),
            pl.BlockSpec(
                (1, B, 1, META_COLS),
                lambda k, i: (k, i, 0, 0),
                memory_space=pltpu.SMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, META_COLS),
            lambda k, i: (k, 0, 0),
            memory_space=pltpu.SMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_keys, 1, META_COLS), jnp.int32
        ),
        scratch_shapes=[
            pltpu.VMEM((1, K), jnp.int32),
            pltpu.VMEM((1, K), jnp.int32),
            pltpu.VMEM((1, K), jnp.int32),
        ],
        # Without the explicit per-dimension semantics Mosaic schedules
        # the 2-D grid with a ~4ms per-iteration stall (measured); with
        # it, iterations pipeline properly (~20x faster end-to-end).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(win, meta)
    return out


def pack_steps(steps: ReturnSteps):
    """Host-side (numpy) packing of ReturnSteps for the megakernel: one
    [n, 4, W] window array (occ/f/a/b) + [n, 1, META_COLS] scalars,
    padded up to a multiple of STEP_BLOCK. No device traffic."""
    if steps.NW != 1:
        raise ValueError("pallas kernel supports a single mask word (W<=32)")
    B = STEP_BLOCK
    if len(steps) % B:
        steps = steps.padded(((len(steps) + B - 1) // B) * B)
    n = len(steps)
    W = steps.W
    bits = slot_bit_table(W)[:, 0]  # [W] int32
    meta = np.zeros((n, 1, META_COLS), np.int32)
    meta[:, 0, 0] = bits[steps.slot]
    meta[:, 0, 1] = steps.live.astype(np.int32)
    meta[:, 0, 2] = steps.crashed[:, 0]
    meta[:, 0, 3] = steps.op_index
    meta[:, 0, 4] = steps.init_state
    win = np.stack(
        [steps.occ.astype(np.int32), steps.f, steps.a, steps.b], axis=1
    )
    return win, meta


def steps_pallas_args(steps: ReturnSteps) -> tuple:
    """Device args for a single-key check: a batch of one (the kernel
    is always batched)."""
    win, meta = pack_steps(steps)
    return jnp.asarray(win[None]), jnp.asarray(meta[None])


def check_steps_pallas(
    steps: ReturnSteps,
    model: str = "cas-register",
    K: int = 128,
    interpret: bool = False,
) -> Tuple[bool, bool, int]:
    """Run the megakernel over precompiled return steps:
    (alive, overflow, died_op_index). Same verdict contract as
    wgl_jax.check_steps_jax.

    The packed+uploaded device args are memoized on the steps object:
    escalation-ladder rungs change only K, so re-running at a bigger K
    must not re-pack or re-upload the (potentially tens of MB) step
    arrays through the host-device link."""
    from jepsen_tpu.checker.events import memo_on

    args = memo_on(
        steps, "_pallas_args", None, lambda: steps_pallas_args(steps)
    )
    out = _pallas_scan(
        *args,
        model_name=model if isinstance(model, str) else model.name,
        K=K,
        W=steps.W,
        interpret=interpret,
    )
    out = np.asarray(out)[:, 0, :]
    return bool(out[0, 0]), bool(out[0, 1]), int(out[0, 2])


def check_keys_pallas(
    steps_list,
    model: str = "cas-register",
    K: int = 128,
    interpret: bool = False,
):
    """Check many per-key ReturnSteps with ONE host round-trip: all
    per-key kernels are dispatched asynchronously (they queue
    back-to-back on the device) and the host syncs once at the end —
    so the host round-trip cost amortizes over the whole key batch
    instead of being paid per key. All steps must share W (bucketed by
    the caller); lengths pad to a common bucket so one compiled kernel
    serves every key. Returns [(alive, overflow, died_op_index)]."""
    B = STEP_BLOCK
    n = max(max(len(st) for st in steps_list), 1)
    # Power-of-two bucket (not just a STEP_BLOCK multiple): one Mosaic
    # compile serves every batch length in the bucket, like the
    # single-key path.
    from jepsen_tpu.checker.events import bucket

    n = bucket(n, 64)
    name = model if isinstance(model, str) else model.name
    wins, metas = [], []
    for st in steps_list:
        w, m = pack_steps(st.padded(n))
        wins.append(w)
        metas.append(m)
    out = np.asarray(
        _pallas_scan(
            jnp.asarray(np.stack(wins)),
            jnp.asarray(np.stack(metas)),
            model_name=name,
            K=K,
            W=steps_list[0].W,
            interpret=interpret,
        )
    )[:, 0, :]
    return [
        (bool(o[0]), bool(o[1]), int(o[2])) for o in out
    ]
