"""Pallas kernels for the NoC router hot loop (paper's hot spot).

Two kernels, both equivalence-tested flit-for-flit against the jnp
reference engine (``repro.core.noc_sim.router``):

* :func:`router_arbiter_pallas` — phase-B only: round-robin arbitration
  of routed input heads into free output registers with wormhole burst
  locking, for a TILE of routers held in VMEM (``backend="pallas"``).
* :func:`fused_fabric_step_pallas` — the row-local half of the one-cycle
  network update (``backend="pallas_fused"``): arbitration, the
  output-register update and the input-FIFO pop/push, in ONE kernel
  over an ``(N, P*D*F)``-flattened row layout.  ``N`` is routers with
  every physical channel folded into extra rows, so one kernel launch
  per simulated cycle updates every router of every channel.  The
  cross-row half — output-register drain, the neighbor push through
  the inverse link map, NI injection and the route-table lookup — is
  :func:`repro.core.noc_sim.router.fabric_front` in jnp, from the
  cycle-start state, and reaches the kernel as ``(N, P)`` operands.

Both kernels compile for TPU v5e through Mosaic
(``tests/test_tpu_compile.py`` compiles them for a described v5e at the
paper's mesh sizes).  Mosaic accepts 2-D arrays, static slices, integer
min/sum/any reductions and elementwise math here, so the kernels use
nothing else: no in-kernel gather, no integer argmax, no 3-D reshape.
Interpret mode is for the CPU only (``interpret=None`` picks it there);
on a TPU the kernels always compile.

Arbiter layout (R routers, P ports, blocked over R):
  out_port  (R, P) int32   routed output port per input head (99: empty)
  beat      (R, P) int32   remaining burst beats per input head
  rr_ptr    (R, P) int32   per-output round-robin pointer
  oreg_free (R, P) int32   output register accepts this cycle
  lock_in   (R, P) int32   wormhole lock (input idx or -1)
outputs:
  winner    (R, P) int32   granted input per output (-1: none)
  pop       (R, P) int32   input head consumed
  new_ptr   (R, P) int32   (advances only on unlocked grants, like the
                           engine)
  new_lock  (R, P) int32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NO = 99

# VMEM budget for the no-grid fused kernel: every operand and output
# lives in VMEM at once, so a real Mosaic lowering of an oversized
# fabric dies with an opaque allocator error deep inside the compiler.
# 16 MiB is Mosaic's default scoped-VMEM limit on v5e.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def _interpret_default() -> bool:
    """Interpret mode is for the CPU only: on an accelerator the kernels
    compile (Mosaic), and a platform they cannot compile for fails
    loudly instead of silently interpreting."""
    return jax.default_backend() == "cpu"


def _arbitrate(out_port, beat, ptr, free, lock, *, n_ports: int):
    """Shared phase-B math: ``free``/``lock``/``ptr`` per OUT port,
    ``out_port``/``beat`` per IN head, all ``(rows, P)``.  Returns
    ``(winner, pop, new_ptr, new_lock)``.

    One static pass per output port over ``(rows, P)`` arrays (inputs
    on the lane axis), so every intermediate stays 2-D — a ``(rows, P,
    P)`` one would take a whole (8, 128) VMEM tile per row.  Mosaic has
    no integer argmax: the winner is the least input id among the
    requests holding the best score (scores are distinct, so that is
    the one)."""
    P = n_ports
    rows = out_port.shape[0]
    i_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, P), 1)
    winner = jnp.full((rows, P), -1, jnp.int32)
    pop = jnp.zeros((rows, P), jnp.bool_)
    new_ptr, new_lock = ptr, lock
    for o in range(P):
        lock_o = lock[:, o:o + 1]
        req = (out_port == o) & free[:, o:o + 1]
        req &= (lock_o < 0) | (i_ids == lock_o)
        score = jnp.where(req, (i_ids - ptr[:, o:o + 1]) % P, NO)
        best = jnp.min(score, axis=1, keepdims=True)
        granted = best < NO
        w = jnp.min(jnp.where(req & (score == best), i_ids, P), axis=1,
                    keepdims=True)
        w = jnp.where(granted, w, -1)                        # (rows, 1)
        hit = i_ids == w
        pop |= hit
        w_beat = jnp.sum(jnp.where(hit, beat, 0), axis=1, keepdims=True)
        this = i_ids == o
        winner = jnp.where(this, w, winner)
        # rr pointer holds while an output is wormhole-locked
        new_ptr = jnp.where(this & granted & (lock_o < 0), (w + 1) % P,
                            new_ptr)
        new_lock = jnp.where(this & granted,
                             jnp.where(w_beat > 1, w, -1), new_lock)
    return winner, pop, new_ptr, new_lock


# --------------------------------------------------------------------- #
# phase-B arbiter kernel (backend="pallas")
# --------------------------------------------------------------------- #
def _arb_kernel(oport_ref, beat_ref, ptr_ref, free_ref, lock_ref,
                win_ref, pop_ref, nptr_ref, nlock_ref, *, n_ports: int):
    winner, pop, new_ptr, new_lock = _arbitrate(
        oport_ref[...], beat_ref[...], ptr_ref[...], free_ref[...] > 0,
        lock_ref[...], n_ports=n_ports)
    win_ref[...] = winner
    pop_ref[...] = pop.astype(jnp.int32)
    nptr_ref[...] = new_ptr
    nlock_ref[...] = new_lock


def _pad_rows(R: int, block_r: int) -> tuple[int, int]:
    """(block, padded R): pad the row axis up to a block multiple with
    neutral rows instead of degrading the tile (a prime R used to fall
    all the way to ``block_r=1``).  Neutral rows (``out_port=NO``,
    ``oreg_free=0``, ``lock_in=-1``) are safe: empty heads never
    request, so they arbitrate to nothing and are sliced off."""
    b = min(block_r, R)
    return b, -(-R // b) * b


def router_arbiter_pallas(out_port, beat, rr_ptr, oreg_free, lock_in,
                          *, block_r: int = 8, interpret: bool | None = None):
    """Phase-B arbitration for all routers; same contract as
    :func:`repro.core.noc_sim.router.arbiter_jnp` (``oreg_free`` may be
    bool or int mask; ``pop`` comes back as int32 0/1).

    ``interpret=None`` selects interpreter mode on the CPU only.
    """
    R, P = out_port.shape
    if interpret is None:
        interpret = _interpret_default()
    block_r, R_pad = _pad_rows(R, block_r)
    grid = (R_pad // block_r,)

    def pad(a, fill):
        a = a.astype(jnp.int32)
        if R_pad == R:
            return a
        return jnp.concatenate(
            [a, jnp.full((R_pad - R, P), fill, jnp.int32)], axis=0)

    kernel = functools.partial(_arb_kernel, n_ports=P)
    spec = pl.BlockSpec((block_r, P), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=[spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((R_pad, P), jnp.int32)] * 4,
        interpret=interpret,
    )(pad(out_port, NO), pad(beat, 1), pad(rr_ptr, 0),
      pad(oreg_free, 0), pad(lock_in, -1))
    return tuple(o[:R] for o in out)


# --------------------------------------------------------------------- #
# fused router-update kernel (backend="pallas_fused")
# --------------------------------------------------------------------- #
def _fused_kernel(fifo_ref, count_ref, ptr_ref, oreg_ref, oregv_ref,
                  lock_ref, drain_ref, oport_ref, rv_ref, rflit_ref,
                  depth_ref, nfifo_ref, ncount_ref, nptr_ref, noreg_ref,
                  noregv_ref, nlock_ref, *, n_ports: int, d_max: int,
                  n_fields: int, f_beat: int):
    P, D, F = n_ports, d_max, n_fields

    def flit(ref, i):
        """Flit ``i`` of a row's lane slab (a static lane slice)."""
        return ref[:, i * F:(i + 1) * F]

    drain = drain_ref[...] > 0
    oreg_v = oregv_ref[...] > 0
    # head flit of input port i is slot 0 of its D-slot FIFO
    heads = [flit(fifo_ref, i * D) for i in range(P)]
    beat = jnp.concatenate([h[:, f_beat:f_beat + 1] for h in heads], axis=1)

    # phase B: arbitration into freed output registers
    winner, pop, new_ptr, new_lock = _arbitrate(
        oport_ref[...], beat, ptr_ref[...], (~oreg_v) | drain,
        lock_ref[...], n_ports=P)
    nptr_ref[...] = new_ptr
    nlock_ref[...] = new_lock
    noregv_ref[...] = ((oreg_v & ~drain) | (winner >= 0)).astype(jnp.int32)
    for o in range(P):
        w = winner[:, o:o + 1]
        out = flit(oreg_ref, o)
        for i in range(P):
            out = jnp.where(w == i, heads[i], out)
        noreg_ref[:, o * F:(o + 1) * F] = out

    # input FIFO update: pop then push
    count = count_ref[...] - pop.astype(jnp.int32)
    write = (rv_ref[...] > 0) & (count < depth_ref[...])
    ncount_ref[...] = count + write.astype(jnp.int32)
    slot = jnp.clip(count, 0, D - 1)
    for i in range(P):
        pop_i, write_i = pop[:, i:i + 1], write[:, i:i + 1]
        slot_i = slot[:, i:i + 1]
        recv = flit(rflit_ref, i)
        for d in range(D):
            cur = flit(fifo_ref, i * D + d)
            nxt = (flit(fifo_ref, i * D + d + 1) if d + 1 < D
                   else jnp.zeros_like(cur))
            cur = jnp.where(pop_i, nxt, cur)
            cur = jnp.where(write_i & (slot_i == d), recv, cur)
            nfifo_ref[:, (i * D + d) * F:(i * D + d + 1) * F] = cur


def fused_fabric_step_pallas(fifo, count, rr_ptr, oreg, oreg_v, lock_in,
                             drain, out_port, recv_valid, recv_flit,
                             depth_rows, *, interpret: bool | None = None,
                             vmem_budget_bytes: int | None =
                             VMEM_BUDGET_BYTES):
    """The row-local half of one fabric cycle for ``N`` stacked router
    rows (channels folded into rows by the caller; see
    ``repro.noc.backends``): arbitration into freed output registers,
    the output-register update and the input-FIFO pop/push — the same
    update as :func:`repro.core.noc_sim.router.fabric_update`.

    The cross-row half arrives as operands, computed by
    :func:`repro.core.noc_sim.router.fabric_front` from the cycle-start
    state: ``drain (N, P)`` (output registers that move), ``out_port
    (N, P)`` (routed output per input head, ``NO`` when empty) and the
    neighbor push / NI injection ``recv_valid (N, P)``, ``recv_flit
    (N, P, F)``.  So the kernel reads nothing outside its own row and
    holds no route table.

    State arrives in the engine's logical shapes — ``fifo (N, P, D, F)``,
    ``oreg (N, P, F)``, the rest ``(N, P)`` — and is flattened to the
    kernel's 2D ``(N, P*D*F)`` / ``(N, P*F)`` lane layout here; heads
    and FIFO slots are static lane slices of it.  ``depth_rows (N,)``
    is the traced per-row FIFO depth (<= the static ``D``).

    When compiling for a real TPU (``interpret=False``) the kernel is
    no-grid — every operand and output is resident in VMEM at once — so
    the total footprint is checked against ``vmem_budget_bytes`` up
    front and an over-budget fabric raises a ``ValueError`` carrying
    the byte estimate and resharding hints instead of an opaque Mosaic
    allocator failure.  ``vmem_budget_bytes=None`` disables the check.

    Returns ``(fifo, count, rr_ptr, oreg, oreg_v (int32), lock_in)``.
    """
    from repro.core.noc_sim.router import F_BEAT

    N, P, D, F = fifo.shape
    if interpret is None:
        interpret = _interpret_default()

    kernel = functools.partial(_fused_kernel, n_ports=P, d_max=D,
                               n_fields=F, f_beat=F_BEAT)
    out_shapes = [
        jax.ShapeDtypeStruct((N, P * D * F), jnp.int32),   # fifo
        jax.ShapeDtypeStruct((N, P), jnp.int32),           # count
        jax.ShapeDtypeStruct((N, P), jnp.int32),           # rr_ptr
        jax.ShapeDtypeStruct((N, P * F), jnp.int32),       # oreg
        jax.ShapeDtypeStruct((N, P), jnp.int32),           # oreg_v
        jax.ShapeDtypeStruct((N, P), jnp.int32),           # lock_in
    ]
    operands = [
        fifo.reshape(N, P * D * F), count, rr_ptr,
        oreg.reshape(N, P * F), oreg_v, lock_in, drain, out_port,
        recv_valid, recv_flit.reshape(N, P * F), depth_rows[:, None]]
    operands = [o.astype(jnp.int32) for o in operands]
    if not interpret and vmem_budget_bytes is not None:
        # each 2-D int32 array occupies whole (8, 128) VMEM tiles
        est = sum(4 * -(-r // 8) * 8 * -(-c // 128) * 128
                  for r, c in [o.shape for o in operands + out_shapes])
        if est > vmem_budget_bytes:
            raise ValueError(
                f"fused fabric kernel needs ~{est} bytes of VMEM for "
                f"{N} router rows (P={P}, D={D}, budget "
                f"{vmem_budget_bytes}); the no-grid kernel holds the "
                f"whole fabric resident.  Shrink the resident slab — "
                f"row-shard the mesh across devices "
                f"(simulate(..., shard=RowShard(n))), lower the padded "
                f"FIFO depth (depth sweeps pad every spec to the max "
                f"depth), or split physical channels into separate "
                f"sims — or raise vmem_budget_bytes if your core "
                f"really has the headroom.")
    nfifo, ncount, nptr, noreg, noregv, nlock = pl.pallas_call(
        kernel, out_shape=out_shapes, interpret=interpret)(*operands)
    return (nfifo.reshape(N, P, D, F), ncount, nptr,
            noreg.reshape(N, P, F), noregv, nlock)


def router_arbiter_ref(out_port, beat, rr_ptr, oreg_free, lock_in):
    """jnp oracle — the engine's own arbitration, int-typed like the
    kernel outputs."""
    from repro.core.noc_sim.router import arbiter_jnp
    winner, pop, new_ptr, new_lock = arbiter_jnp(
        jnp.asarray(out_port, jnp.int32), jnp.asarray(beat, jnp.int32),
        jnp.asarray(rr_ptr, jnp.int32), jnp.asarray(oreg_free),
        jnp.asarray(lock_in, jnp.int32))
    return winner, pop.astype(jnp.int32), new_ptr, new_lock
