"""Pallas ring attention: KV rotation via explicit inter-chip RDMA (fwd+bwd).

The shard_map ring in :mod:`maggy_tpu.parallel.ringattention` leaves the
KV rotation to XLA's ``ppermute`` scheduling. This kernel issues the rotation
itself with ``pltpu.make_async_remote_copy`` and overlaps it with the block
compute explicitly: at ring step ``s`` each device STARTS the RDMA of its
current KV chunk to its right neighbor, computes online-softmax attention on
that same chunk while the copy is in flight, then acknowledges consumption so
the left neighbor may overwrite the just-freed slot (2-slot double buffer with
per-cell flow control — no global lockstep).

The BACKWARD is a ring kernel too (``jax.custom_vjp`` wired in
:func:`ring_flash_attention`): q/o/do and the saved per-row LSE stay local;
(k, v, dk, dv) rotate together. At each step a device recomputes the
probabilities of its q shard against the visiting KV chunk from the LSE
(FlashAttention-2 recurrence — no [S, S] matrix anywhere), accumulates dQ
locally and folds its dK/dV contribution into the accumulators traveling WITH
the chunk. k/v sends still overlap the compute (read-only); dk/dv sends start
right after it and overlap the next step's receive+compute. The final
rotation delivers each chunk's finished dK/dV straight into its home device's
output buffer.

Memory plan (VMEM is ~16MB/core): q/o and the f32 accumulators live in HBM
(``pl.ANY``); the kernel stages one q row-tile and one KV chunk at a time
into VMEM scratch. Communication buffers are per-(batch, kv-head) HBM slots so
grid cells may skew across devices without clobbering each other. Causal runs
skip fully-masked chunks (the compute, not the rotation).

No equivalent exists in the reference (SURVEY.md §5.7 — sequence parallelism
is absent there); the layout matches ``parallel/ringattention.py`` so the two
implementations are interchangeable and cross-checked in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

def _neighbor(mesh, axis_name: str, offset: int):
    """Mesh coordinates of the ring neighbor at ``offset`` along ``axis_name``
    (same pattern as pallas's reference all-gather kernel)."""
    idx = lax.axis_index(axis_name)
    size = dict(mesh.shape)[axis_name]
    nxt = lax.rem(idx + offset + size, size)
    return tuple(
        nxt if name == axis_name else lax.axis_index(name)
        for name in mesh.axis_names
    )


def _ring_kernel(
    q_ref,       # ANY [B, C, KH, G, D]
    k_ref,       # ANY [B, C, KH, D]
    v_ref,       # ANY [B, C, KH, D]
    o_ref,       # ANY [B, C, KH, G, D]
    kbuf,        # ANY [B, KH, 2, C, D]   ring comm buffer (k)
    vbuf,        # ANY [B, KH, 2, C, D]   ring comm buffer (v)
    acc_ref,     # ANY [B, C, KH, G, D] f32
    m_ref,       # ANY [B, C, KH, G] f32
    l_ref,       # ANY [B, C, KH, G] f32
    q_st,        # VMEM [QT, G, D]
    k_st,        # VMEM [C, D]
    v_st,        # VMEM [C, D]
    acc_st,      # VMEM [QT, G, D] f32
    ml_st,       # VMEM [2, QT, G] f32   (m, l)
    send_k,      # DMA sems [B, KH]
    send_v,
    recv_k,      # DMA sems [B, KH, 2]
    recv_v,
    ack,         # REGULAR sems [B, KH]
    copy_sem,    # DMA sems [8] for local HBM<->VMEM staging
    *,
    mesh,
    axis_name: str,
    num_shards: int,
    causal: bool,
    q_tile: int,
):
    b = pl.program_id(0)
    kh = pl.program_id(1)
    C = k_st.shape[0]
    G = q_st.shape[1]
    n_qt = C // q_tile
    my = lax.axis_index(axis_name)
    left = _neighbor(mesh, axis_name, -1)
    right = _neighbor(mesh, axis_name, +1)
    scale = 1.0 / (q_st.shape[2] ** 0.5)

    # one barrier per kernel launch: neighbors must have entered the kernel
    # (buffers out of their previous op's live ranges) before any RDMA lands
    @pl.when((b == 0) & (kh == 0))
    def _startup_barrier():
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, 1, device_id=left)
        pltpu.semaphore_signal(bar, 1, device_id=right)
        pltpu.semaphore_wait(bar, 2)

    def _stage_kv(step):
        """current chunk -> VMEM (step 0 reads the local input directly)."""
        cur = lax.rem(step, 2)

        @pl.when(step == 0)
        def _():
            cp_k = pltpu.make_async_copy(
                k_ref.at[b, :, kh, :], k_st, copy_sem.at[0]
            )
            cp_v = pltpu.make_async_copy(
                v_ref.at[b, :, kh, :], v_st, copy_sem.at[1]
            )
            cp_k.start(); cp_v.start(); cp_k.wait(); cp_v.wait()

        @pl.when(step > 0)
        def _():
            cp_k = pltpu.make_async_copy(kbuf.at[b, kh, cur], k_st, copy_sem.at[0])
            cp_v = pltpu.make_async_copy(vbuf.at[b, kh, cur], v_st, copy_sem.at[1])
            cp_k.start(); cp_v.start(); cp_k.wait(); cp_v.wait()

    def _compute_chunk(step):
        """Online-softmax update of every q row-tile against the staged KV
        chunk; runs while this step's RDMA is in flight."""
        src = lax.rem(my - step + num_shards, num_shards)  # owner of the chunk
        k_pos = src * C + lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)

        def tile_body(qt, _):
            row0 = qt * q_tile
            cp_q = pltpu.make_async_copy(
                q_ref.at[b, pl.ds(row0, q_tile), kh], q_st, copy_sem.at[2]
            )
            cp_q.start()

            @pl.when(step == 0)
            def _():
                acc_st[...] = jnp.zeros_like(acc_st)
                ml_st[0] = jnp.full_like(ml_st[0], NEG_INF)
                ml_st[1] = jnp.zeros_like(ml_st[1])

            @pl.when(step > 0)
            def _():
                cp_a = pltpu.make_async_copy(
                    acc_ref.at[b, pl.ds(row0, q_tile), kh], acc_st, copy_sem.at[3]
                )
                cp_m = pltpu.make_async_copy(
                    m_ref.at[b, pl.ds(row0, q_tile), kh], ml_st.at[0], copy_sem.at[4]
                )
                cp_l = pltpu.make_async_copy(
                    l_ref.at[b, pl.ds(row0, q_tile), kh], ml_st.at[1], copy_sem.at[5]
                )
                cp_a.start(); cp_m.start(); cp_l.start()
                cp_a.wait(); cp_m.wait(); cp_l.wait()

            cp_q.wait()

            q = q_st[...].astype(jnp.float32)          # [QT, G, D]
            k = k_st[...].astype(jnp.float32)          # [C, D]
            logits = jax.lax.dot_general(
                q.reshape(q_tile * G, -1), k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(q_tile, G, C) * scale            # [QT, G, C]
            if causal:
                q_pos = (
                    my * C + row0
                    + lax.broadcasted_iota(jnp.int32, (q_tile, 1, 1), 0)
                )
                logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)

            m_prev = ml_st[0]                          # [QT, G]
            l_prev = ml_st[1]
            m_new = jnp.maximum(m_prev, logits.max(axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new[..., None])     # [QT, G, C]
            l_new = l_prev * alpha + p.sum(axis=-1)
            pv = jax.lax.dot_general(
                p.reshape(q_tile * G, C), v_st[...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(q_tile, G, -1)
            acc_st[...] = acc_st[...] * alpha[..., None] + pv
            ml_st[0] = m_new
            ml_st[1] = l_new

            # persist accumulators for the next ring step
            cp_a = pltpu.make_async_copy(
                acc_st, acc_ref.at[b, pl.ds(row0, q_tile), kh], copy_sem.at[3]
            )
            cp_m = pltpu.make_async_copy(
                ml_st.at[0], m_ref.at[b, pl.ds(row0, q_tile), kh], copy_sem.at[4]
            )
            cp_l = pltpu.make_async_copy(
                ml_st.at[1], l_ref.at[b, pl.ds(row0, q_tile), kh], copy_sem.at[5]
            )
            cp_a.start(); cp_m.start(); cp_l.start()
            cp_a.wait(); cp_m.wait(); cp_l.wait()
            return 0

        lax.fori_loop(0, n_qt, tile_body, 0)

    def _rdma_desc(s, buf, s_sem, r_sem):
        """The descriptor of the RDMA started at step ``s`` — every device
        runs the same program, so waiting on OUR descriptor's recv side waits
        for the LEFT neighbor's symmetric send to land (the same SPMD idiom as
        pallas's reference all-gather kernel)."""
        src = lax.rem(s, 2)
        dst = lax.rem(s + 1, 2)
        return pltpu.make_async_remote_copy(
            buf.at[b, kh, src], buf.at[b, kh, dst],
            s_sem.at[b, kh], r_sem.at[b, kh, dst],
            device_id=right,
        )

    def step_body(s, _):
        cur = lax.rem(s, 2)
        nxt = lax.rem(s + 1, 2)

        # chunk s arrived? (step 0 computes on the local input)
        @pl.when(s > 0)
        def _():
            _rdma_desc(s - 1, kbuf, send_k, recv_k).wait_recv()
            _rdma_desc(s - 1, vbuf, send_v, recv_v).wait_recv()

        _stage_kv(s)

        # rotate: start sending the chunk we hold, then compute on it
        @pl.when(s < num_shards - 1)
        def _():
            # flow control: right must have consumed its `nxt` slot (its
            # compute of step s-1); its ack arrives on OUR ack sem
            @pl.when(s > 0)
            def _():
                pltpu.semaphore_wait(ack.at[b, kh], 1)

            def _send(src_first, src_later, buf, s_sem, r_sem):
                @pl.when(s == 0)
                def _():
                    pltpu.make_async_remote_copy(
                        src_first, buf.at[b, kh, nxt],
                        s_sem.at[b, kh], r_sem.at[b, kh, nxt],
                        device_id=right,
                    ).start()

                @pl.when(s > 0)
                def _():
                    pltpu.make_async_remote_copy(
                        src_later, buf.at[b, kh, nxt],
                        s_sem.at[b, kh], r_sem.at[b, kh, nxt],
                        device_id=right,
                    ).start()

            _send(k_ref.at[b, :, kh, :], kbuf.at[b, kh, cur], kbuf, send_k, recv_k)
            _send(v_ref.at[b, :, kh, :], vbuf.at[b, kh, cur], vbuf, send_v, recv_v)

        # the overlapped work: attention on the chunk while RDMA flies
        src = lax.rem(my - s + num_shards, num_shards)
        skip = causal & (src > my)  # chunk entirely in the causal future

        @pl.when(jnp.logical_not(skip))
        def _():
            _compute_chunk(s)

        @pl.when(s < num_shards - 1)
        def _():
            # outgoing copy must have left our buffer before the left
            # neighbor is allowed to overwrite it (our ack)
            _rdma_desc(s, kbuf, send_k, recv_k).wait_send()
            _rdma_desc(s, vbuf, send_v, recv_v).wait_send()

        # acks consumed at steps 1..N-2 by the left's sender — produce exactly
        # that many (a leftover count would fail the kernel's sem-drain check)
        @pl.when(s < num_shards - 2)
        def _():
            pltpu.semaphore_signal(ack.at[b, kh], 1, device_id=left)

        return 0

    lax.fori_loop(0, num_shards, step_body, 0)

    # finalize: o = acc / l
    def out_tile(qt, _):
        row0 = qt * q_tile
        cp_a = pltpu.make_async_copy(
            acc_ref.at[b, pl.ds(row0, q_tile), kh], acc_st, copy_sem.at[3]
        )
        cp_l = pltpu.make_async_copy(
            l_ref.at[b, pl.ds(row0, q_tile), kh], ml_st.at[1], copy_sem.at[5]
        )
        cp_a.start(); cp_l.start(); cp_a.wait(); cp_l.wait()
        l = jnp.maximum(ml_st[1], 1e-30)[..., None]
        q_st[...] = (acc_st[...] / l).astype(q_st.dtype)  # reuse q staging
        cp_o = pltpu.make_async_copy(
            q_st, o_ref.at[b, pl.ds(row0, q_tile), kh], copy_sem.at[6]
        )
        cp_o.start(); cp_o.wait()
        return 0

    lax.fori_loop(0, n_qt, out_tile, 0)


def _ring_flash_local(q, k, v, *, mesh, axis_name, num_shards, causal,
                      q_tile, interpret, return_stats=False):
    """Per-device body (under shard_map): q [B, C, H, D], k/v [B, C, KH, D].
    ``return_stats`` also returns the running-softmax (m, l) — the backward
    derives its per-row LSE residual from them."""
    B, C, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, C, KH, G, D)

    kernel = functools.partial(
        _ring_kernel,
        mesh=mesh,
        axis_name=axis_name,
        num_shards=num_shards,
        causal=causal,
        q_tile=q_tile,
    )
    f32 = jnp.float32
    out_shapes = (
        jax.ShapeDtypeStruct((B, C, KH, G, D), q.dtype),   # o
        jax.ShapeDtypeStruct((B, KH, 2, C, D), k.dtype),   # kbuf
        jax.ShapeDtypeStruct((B, KH, 2, C, D), v.dtype),   # vbuf
        jax.ShapeDtypeStruct((B, C, KH, G, D), f32),       # acc
        jax.ShapeDtypeStruct((B, C, KH, G), f32),          # m
        jax.ShapeDtypeStruct((B, C, KH, G), f32),          # l
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    o = pl.pallas_call(
        kernel,
        grid=(B, KH),
        in_specs=[any_spec] * 3,
        out_specs=[any_spec] * 6,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((q_tile, G, D), q.dtype),       # q_st
            pltpu.VMEM((C, D), k.dtype),               # k_st
            pltpu.VMEM((C, D), v.dtype),               # v_st
            pltpu.VMEM((q_tile, G, D), f32),           # acc_st
            pltpu.VMEM((2, q_tile, G), f32),           # ml_st
            pltpu.SemaphoreType.DMA((B, KH)),          # send_k
            pltpu.SemaphoreType.DMA((B, KH)),          # send_v
            pltpu.SemaphoreType.DMA((B, KH, 2)),       # recv_k
            pltpu.SemaphoreType.DMA((B, KH, 2)),       # recv_v
            pltpu.SemaphoreType.REGULAR((B, KH)),      # ack
            pltpu.SemaphoreType.DMA((8,)),             # local staging sems
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=7, has_side_effects=True
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(qg, k, v)
    if return_stats:
        return o[0].reshape(B, C, H, D), o[4], o[5]
    return o[0].reshape(B, C, H, D)


# -------------------------------------------------------------------- backward


def _ring_bwd_kernel(
    q_ref,       # ANY [B, C, KH, G, D]
    k_ref,       # ANY [B, C, KH, D]
    v_ref,       # ANY [B, C, KH, D]
    o_ref,       # ANY [B, C, KH, G, D]
    do_ref,      # ANY [B, C, KH, G, D]
    lse_ref,     # ANY [B, C, KH, G] f32
    dq_ref,      # ANY [B, C, KH, G, D] f32 (local accumulator + output)
    dkfin,       # ANY [B, C, KH, D] f32 (final dK, delivered by left's RDMA)
    dvfin,       # ANY [B, C, KH, D] f32
    kbuf,        # ANY [B, KH, 2, C, D]       ring comm buffers
    vbuf,        # ANY [B, KH, 2, C, D]
    dkbuf,       # ANY [B, KH, 2, C, D] f32   rotating dK/dV accumulators
    dvbuf,       # ANY [B, KH, 2, C, D] f32
    q_st,        # VMEM [QT, G, D]
    o_st,        # VMEM [QT, G, D]
    do_st,       # VMEM [QT, G, D]
    dq_st,       # VMEM [QT, G, D] f32
    lse_st,      # VMEM [QT, G] f32
    k_st,        # VMEM [C, D]
    v_st,        # VMEM [C, D]
    dk_st,       # VMEM [C, D] f32
    dv_st,       # VMEM [C, D] f32
    send_k,      # DMA sems [B, KH]
    send_v,
    send_dk,
    send_dv,
    recv_k,      # DMA sems [B, KH, 2]
    recv_v,
    recv_dk,
    recv_dv,
    recv_dkf,    # DMA sems [B, KH] (final home delivery)
    recv_dvf,
    ack_kv,      # REGULAR sems [B, KH]
    ack_dkv,
    copy_sem,    # DMA sems [10] local HBM<->VMEM staging
    *,
    mesh,
    axis_name: str,
    num_shards: int,
    causal: bool,
    q_tile: int,
):
    b = pl.program_id(0)
    kh = pl.program_id(1)
    C = k_st.shape[0]
    G = q_st.shape[1]
    n_qt = C // q_tile
    my = lax.axis_index(axis_name)
    left = _neighbor(mesh, axis_name, -1)
    right = _neighbor(mesh, axis_name, +1)
    scale = 1.0 / (q_st.shape[2] ** 0.5)

    @pl.when((b == 0) & (kh == 0))
    def _startup_barrier():
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, 1, device_id=left)
        pltpu.semaphore_signal(bar, 1, device_id=right)
        pltpu.semaphore_wait(bar, 2)

    def _stage(step):
        """Visiting chunk (k, v) + its traveling (dk, dv) accumulators ->
        VMEM. Step 0 reads the local input; dk/dv start at zero there."""
        cur = lax.rem(step, 2)

        @pl.when(step == 0)
        def _():
            cp_k = pltpu.make_async_copy(k_ref.at[b, :, kh, :], k_st, copy_sem.at[0])
            cp_v = pltpu.make_async_copy(v_ref.at[b, :, kh, :], v_st, copy_sem.at[1])
            cp_k.start(); cp_v.start(); cp_k.wait(); cp_v.wait()
            dk_st[...] = jnp.zeros_like(dk_st)
            dv_st[...] = jnp.zeros_like(dv_st)

        @pl.when(step > 0)
        def _():
            cps = [
                pltpu.make_async_copy(kbuf.at[b, kh, cur], k_st, copy_sem.at[0]),
                pltpu.make_async_copy(vbuf.at[b, kh, cur], v_st, copy_sem.at[1]),
                pltpu.make_async_copy(dkbuf.at[b, kh, cur], dk_st, copy_sem.at[2]),
                pltpu.make_async_copy(dvbuf.at[b, kh, cur], dv_st, copy_sem.at[3]),
            ]
            for cp in cps:
                cp.start()
            for cp in cps:
                cp.wait()

    def _compute_chunk(step):
        """dQ / dK / dV contributions of every local q row-tile against the
        staged chunk, probabilities recomputed from the saved LSE."""
        src = lax.rem(my - step + num_shards, num_shards)
        k_pos = src * C + lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)
        k = k_st[...].astype(jnp.float32)          # [C, D]
        v = v_st[...].astype(jnp.float32)

        def tile_body(qt, _):
            row0 = qt * q_tile
            cps = [
                pltpu.make_async_copy(
                    q_ref.at[b, pl.ds(row0, q_tile), kh], q_st, copy_sem.at[4]
                ),
                pltpu.make_async_copy(
                    o_ref.at[b, pl.ds(row0, q_tile), kh], o_st, copy_sem.at[5]
                ),
                pltpu.make_async_copy(
                    do_ref.at[b, pl.ds(row0, q_tile), kh], do_st, copy_sem.at[6]
                ),
                pltpu.make_async_copy(
                    lse_ref.at[b, pl.ds(row0, q_tile), kh], lse_st, copy_sem.at[7]
                ),
            ]
            for cp in cps:
                cp.start()

            @pl.when(step == 0)
            def _():
                dq_st[...] = jnp.zeros_like(dq_st)

            @pl.when(step > 0)
            def _():
                cp_dq = pltpu.make_async_copy(
                    dq_ref.at[b, pl.ds(row0, q_tile), kh], dq_st, copy_sem.at[8]
                )
                cp_dq.start(); cp_dq.wait()

            for cp in cps:
                cp.wait()

            q = q_st[...].astype(jnp.float32)      # [QT, G, D]
            do = do_st[...].astype(jnp.float32)
            o = o_st[...].astype(jnp.float32)
            logits = jax.lax.dot_general(
                q.reshape(q_tile * G, -1), k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(q_tile, G, C) * scale        # [QT, G, C]
            # probabilities from the saved LSE (lse=+inf rows -> p=0)
            p = jnp.exp(logits - lse_st[...][..., None])
            if causal:
                q_pos = (
                    my * C + row0
                    + lax.broadcasted_iota(jnp.int32, (q_tile, 1, 1), 0)
                )
                p = jnp.where(q_pos >= k_pos, p, 0.0)
            dp = jax.lax.dot_general(
                do.reshape(q_tile * G, -1), v,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(q_tile, G, C)
            delta = jnp.sum(do * o, axis=-1)       # [QT, G]
            ds = p * (dp - delta[..., None]) * scale

            dq_st[...] = dq_st[...] + jax.lax.dot_general(
                ds.reshape(q_tile * G, C), k,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(q_tile, G, -1)
            # dK += dS^T Q ; dV += P^T dO — contract the q-row dim (GQA groups
            # fold into the same contraction, summing the group for free)
            dk_st[...] = dk_st[...] + jax.lax.dot_general(
                ds.reshape(q_tile * G, C), q.reshape(q_tile * G, -1),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dv_st[...] = dv_st[...] + jax.lax.dot_general(
                p.reshape(q_tile * G, C), do.reshape(q_tile * G, -1),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

            cp_dq = pltpu.make_async_copy(
                dq_st, dq_ref.at[b, pl.ds(row0, q_tile), kh], copy_sem.at[8]
            )
            cp_dq.start(); cp_dq.wait()
            return 0

        lax.fori_loop(0, n_qt, tile_body, 0)

    def _rdma_desc(s, buf, s_sem, r_sem):
        src = lax.rem(s, 2)
        dst = lax.rem(s + 1, 2)
        return pltpu.make_async_remote_copy(
            buf.at[b, kh, src], buf.at[b, kh, dst],
            s_sem.at[b, kh], r_sem.at[b, kh, dst],
            device_id=right,
        )

    def _fin_desc(buf, fin, s_sem, r_sem):
        """Last rotation: the finished dK/dV chunk goes straight into its home
        device's output buffer (we hold right's chunk at step N-1)."""
        src = lax.rem(num_shards - 1, 2)
        return pltpu.make_async_remote_copy(
            buf.at[b, kh, src], fin.at[b, :, kh, :],
            s_sem.at[b, kh], r_sem.at[b, kh],
            device_id=right,
        )

    def step_body(s, _):
        cur = lax.rem(s, 2)
        nxt = lax.rem(s + 1, 2)

        @pl.when(s > 0)
        def _():
            _rdma_desc(s - 1, kbuf, send_k, recv_k).wait_recv()
            _rdma_desc(s - 1, vbuf, send_v, recv_v).wait_recv()
            _rdma_desc(s - 1, dkbuf, send_dk, recv_dk).wait_recv()
            _rdma_desc(s - 1, dvbuf, send_dv, recv_dv).wait_recv()

        _stage(s)

        # k/v are read-only: rotate them BEFORE the compute so the RDMA flies
        # under it (same as the forward)
        @pl.when(s < num_shards - 1)
        def _():
            @pl.when(s > 0)
            def _():
                pltpu.semaphore_wait(ack_kv.at[b, kh], 1)

            def _send(src_first, src_later, buf, s_sem, r_sem):
                @pl.when(s == 0)
                def _():
                    pltpu.make_async_remote_copy(
                        src_first, buf.at[b, kh, nxt],
                        s_sem.at[b, kh], r_sem.at[b, kh, nxt],
                        device_id=right,
                    ).start()

                @pl.when(s > 0)
                def _():
                    pltpu.make_async_remote_copy(
                        src_later, buf.at[b, kh, nxt],
                        s_sem.at[b, kh], r_sem.at[b, kh, nxt],
                        device_id=right,
                    ).start()

            _send(k_ref.at[b, :, kh, :], kbuf.at[b, kh, cur], kbuf, send_k, recv_k)
            _send(v_ref.at[b, :, kh, :], vbuf.at[b, kh, cur], vbuf, send_v, recv_v)

        src = lax.rem(my - s + num_shards, num_shards)
        skip = causal & (src > my)  # chunk entirely in the causal future

        @pl.when(jnp.logical_not(skip))
        def _():
            _compute_chunk(s)

        # persist the (possibly pass-through) accumulators into the slot we
        # are about to send from
        cp_dk = pltpu.make_async_copy(dk_st, dkbuf.at[b, kh, cur], copy_sem.at[2])
        cp_dv = pltpu.make_async_copy(dv_st, dvbuf.at[b, kh, cur], copy_sem.at[3])
        cp_dk.start(); cp_dv.start(); cp_dk.wait(); cp_dv.wait()

        # dk/dv rotate AFTER the compute (read-modify-write); the send overlaps
        # the next step's receive + compute
        @pl.when(s < num_shards - 1)
        def _():
            @pl.when(s > 0)
            def _():
                pltpu.semaphore_wait(ack_dkv.at[b, kh], 1)

            _rdma_desc(s, dkbuf, send_dk, recv_dk).start()
            _rdma_desc(s, dvbuf, send_dv, recv_dv).start()

        @pl.when(s == num_shards - 1)
        def _():
            _fin_desc(dkbuf, dkfin, send_dk, recv_dkf).start()
            _fin_desc(dvbuf, dvfin, send_dv, recv_dvf).start()

        @pl.when(s < num_shards - 1)
        def _():
            _rdma_desc(s, kbuf, send_k, recv_k).wait_send()
            _rdma_desc(s, vbuf, send_v, recv_v).wait_send()
            _rdma_desc(s, dkbuf, send_dk, recv_dk).wait_send()
            _rdma_desc(s, dvbuf, send_dv, recv_dv).wait_send()

        @pl.when(s == num_shards - 1)
        def _():
            _fin_desc(dkbuf, dkfin, send_dk, recv_dkf).wait_send()
            _fin_desc(dvbuf, dvfin, send_dv, recv_dvf).wait_send()

        # ack accounting mirrors the forward: consumed by the left's sends at
        # steps 1..N-2, produced after our wait_send at steps 0..N-3
        @pl.when(s < num_shards - 2)
        def _():
            pltpu.semaphore_signal(ack_kv.at[b, kh], 1, device_id=left)
            pltpu.semaphore_signal(ack_dkv.at[b, kh], 1, device_id=left)

        return 0

    lax.fori_loop(0, num_shards, step_body, 0)

    # our own dK/dV land from the left's final rotation
    _fin_desc(dkbuf, dkfin, send_dk, recv_dkf).wait_recv()
    _fin_desc(dvbuf, dvfin, send_dv, recv_dvf).wait_recv()


def _ring_bwd_local(q, k, v, o, do, lse, *, mesh, axis_name, num_shards,
                    causal, q_tile, interpret):
    """Per-device backward body (under shard_map): q/o/do [B, C, H, D],
    k/v [B, C, KH, D], lse [B, C, KH, G] f32 -> (dq, dk, dv)."""
    B, C, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, C, KH, G, D)
    og = o.reshape(B, C, KH, G, D)
    dog = do.reshape(B, C, KH, G, D)

    kernel = functools.partial(
        _ring_bwd_kernel,
        mesh=mesh,
        axis_name=axis_name,
        num_shards=num_shards,
        causal=causal,
        q_tile=q_tile,
    )
    f32 = jnp.float32
    out_shapes = (
        jax.ShapeDtypeStruct((B, C, KH, G, D), f32),       # dq
        jax.ShapeDtypeStruct((B, C, KH, D), f32),          # dkfin
        jax.ShapeDtypeStruct((B, C, KH, D), f32),          # dvfin
        jax.ShapeDtypeStruct((B, KH, 2, C, D), k.dtype),   # kbuf
        jax.ShapeDtypeStruct((B, KH, 2, C, D), v.dtype),   # vbuf
        jax.ShapeDtypeStruct((B, KH, 2, C, D), f32),       # dkbuf
        jax.ShapeDtypeStruct((B, KH, 2, C, D), f32),       # dvbuf
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid=(B, KH),
        in_specs=[any_spec] * 6,
        out_specs=[any_spec] * 7,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((q_tile, G, D), q.dtype),       # q_st
            pltpu.VMEM((q_tile, G, D), o.dtype),       # o_st
            pltpu.VMEM((q_tile, G, D), do.dtype),      # do_st
            pltpu.VMEM((q_tile, G, D), f32),           # dq_st
            pltpu.VMEM((q_tile, G), f32),              # lse_st
            pltpu.VMEM((C, D), k.dtype),               # k_st
            pltpu.VMEM((C, D), v.dtype),               # v_st
            pltpu.VMEM((C, D), f32),                   # dk_st
            pltpu.VMEM((C, D), f32),                   # dv_st
            pltpu.SemaphoreType.DMA((B, KH)),          # send_k
            pltpu.SemaphoreType.DMA((B, KH)),          # send_v
            pltpu.SemaphoreType.DMA((B, KH)),          # send_dk
            pltpu.SemaphoreType.DMA((B, KH)),          # send_dv
            pltpu.SemaphoreType.DMA((B, KH, 2)),       # recv_k
            pltpu.SemaphoreType.DMA((B, KH, 2)),       # recv_v
            pltpu.SemaphoreType.DMA((B, KH, 2)),       # recv_dk
            pltpu.SemaphoreType.DMA((B, KH, 2)),       # recv_dv
            pltpu.SemaphoreType.DMA((B, KH)),          # recv_dkf
            pltpu.SemaphoreType.DMA((B, KH)),          # recv_dvf
            pltpu.SemaphoreType.REGULAR((B, KH)),      # ack_kv
            pltpu.SemaphoreType.REGULAR((B, KH)),      # ack_dkv
            pltpu.SemaphoreType.DMA((10,)),            # local staging sems
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=8, has_side_effects=True
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(qg, k, v, og, dog, lse)
    dq = out[0].reshape(B, C, H, D).astype(q.dtype)
    dk = out[1].astype(k.dtype)
    dv = out[2].astype(v.dtype)
    return dq, dk, dv


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    causal: bool = True,
    axis_name: str = "seq",
    q_tile: int = 256,
    interpret: bool = False,
):
    """Ring attention with in-kernel RDMA rotation — differentiable.

    :param q: [B, S, H, D] sharded on S over ``axis_name``; k/v [B, S, KH, D].
    :param q_tile: VMEM row-tile; the per-device chunk must divide by it.
    :param interpret: run under the TPU interpret machine (CPU testing —
        remote DMAs and semaphores are simulated faithfully).

    Gradients run through :func:`_ring_bwd_kernel` — a second ring in which
    (k, v, dk, dv) rotate together and the probabilities are recomputed from
    the forward's saved LSE, so training at ``sp > 1`` stays on the RDMA path
    both directions (round-2 verdict item 2).
    """
    from jax.sharding import PartitionSpec as P

    num_shards = mesh.shape[axis_name]
    if num_shards == 1:
        from maggy_tpu.ops import attention as ops_attn

        return ops_attn.blockwise_attention(q, k, v, causal=causal)
    chunk = q.shape[1] // num_shards
    tile = min(q_tile, chunk)
    if chunk % tile:
        raise ValueError(f"per-device chunk {chunk} not divisible by q_tile {tile}")

    spec = P(None, axis_name, None, None)
    stat_spec = P(None, axis_name, None, None)
    kw = dict(
        mesh=mesh,
        axis_name=axis_name,
        num_shards=num_shards,
        causal=causal,
        q_tile=tile,
        interpret=interpret,
    )

    def _fwd_stats(q, k, v):
        return shard_map(
            functools.partial(_ring_flash_local, return_stats=True, **kw),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, stat_spec, stat_spec),
            check_vma=False,
        )(q, k, v)

    @jax.custom_vjp
    def attn(q, k, v):
        return _fwd_stats(q, k, v)[0]

    def attn_fwd(q, k, v):
        o, m, l = _fwd_stats(q, k, v)
        # rows with no visible key carry lse=+inf so exp(s - lse) == 0
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
        return o, (q, k, v, o, lse)

    def attn_bwd(res, g):
        q, k, v, o, lse = res
        return shard_map(
            functools.partial(_ring_bwd_local, **kw),
            mesh=mesh,
            in_specs=(spec, spec, spec, spec, spec, stat_spec),
            out_specs=(spec, spec, spec),
            check_vma=False,
        )(q, k, v, o, g.astype(o.dtype), lse)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn(q, k, v)
