"""Selective state-space scan (Mamba): Pallas TPU kernels, forward and
backward, with an XLA formulation elsewhere.

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]

x, dt: [batch, time, channels]; A: [channels, state]; B, C: [batch, time,
state]; D: [channels]; y: [batch, time, channels] in x's dtype.  The
recurrence runs in float32 whatever the inputs are.

In XLA the recurrence either materialises [time, channels, state] in
float32 or takes `time` sequential steps of tiny operations.  The kernels
walk time in chunks with the state of one block of 1024 channels held in
registers:

  - Layout.  A block of 1024 channels is one (8, 128) float32 register, so
    the state of a block is `state` registers (16), and everything a step
    does is elementwise on whole registers: the arrays are viewed
    [batch, time, channels / 1024, 8, 128] (a free reshape), B_t[n] and
    C_t[n] are scalars read from SMEM, and the sum over n is a chain of
    multiply-adds.  The forward needs no cross-lane operation at all.
  - Grid (batch, channel block, time chunk), time innermost and
    sequential; the state crosses chunks in a VMEM scratch.
  - Backward: the forward saves the state at the start of every chunk
    ([batch, chunks, channels, state] float32); the backward kernel walks
    the chunks in reverse, recomputes the chunk's states into VMEM, then
    steps back through it carrying dL/dh.  dB_t[n] and dC_t[n] sum over
    ALL channels: a step reduces its products over sublanes, the chunk's
    rows are reduced over lanes by one matmul with ones (the MXU is idle
    otherwise), and the channel blocks are summed outside.
  - A sequence that is no multiple of the chunk, and channels that are no
    multiple of 1024, are padded with dt = 0, x = 0: a padded step leaves
    the state as it is, a padded channel stays zero.

Off TPU: the interpreter when RAY_TPU_PALLAS_INTERPRET=1, else
`selective_scan_xla`, a `lax.scan` over time inside a scan over
checkpointed chunks (what autodiff saves is one state a chunk).
`dispatch.taken()` holds the path under "selective_scan" and the plan
(chunk, channel block, sequence) under "selective_scan.plan".
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch

F32 = jnp.float32
SUBLANES, LANES = 8, 128
CHANNEL_BLOCK = SUBLANES * LANES    # one float32 register of channels
DEFAULT_CHUNK = 128


# ---------------------------------------------------------------------------
# XLA formulation: also the ground truth of the kernel tests
# ---------------------------------------------------------------------------

def selective_scan_xla(x, dt, A, B, C, D, chunk: int = DEFAULT_CHUNK):
    """The recurrence as written, float32, one step at a time; chunks are
    checkpointed so that the backward holds one state a chunk."""
    out_dtype = x.dtype
    b, t, c = x.shape
    pad = -t % chunk
    x, dt, B, C = (jnp.pad(a.astype(F32), ((0, 0), (0, pad), (0, 0)))
                   for a in (x, dt, B, C))
    A, D = A.astype(F32), D.astype(F32)
    k = (t + pad) // chunk

    def by_chunk(a):        # [b, T, w] -> [k, chunk, b, w]
        return a.reshape(b, k, chunk, -1).transpose(1, 2, 0, 3)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t[..., None] * A) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, c_t) + D * x_t

    @jax.checkpoint
    def one_chunk(h, inp):
        return jax.lax.scan(step, h, inp)

    _, y = jax.lax.scan(one_chunk, jnp.zeros((b, c, A.shape[1]), F32),
                        tuple(by_chunk(a) for a in (x, dt, B, C)))
    y = y.transpose(2, 0, 1, 3).reshape(b, t + pad, c)
    return y[:, :t].astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                chunk: int, state: int, save_states: bool):
    from jax.experimental import pallas as pl

    if save_states:
        hs_ref, h_ref = rest
    else:
        (h_ref,) = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    if save_states:
        hs_ref[...] = h_ref[...]       # the state this chunk starts from
    d = d_ref[...]

    def step(t, h):
        x_t, dt_t = x_ref[t], dt_ref[t]
        dtx = dt_t * x_t
        y = d * x_t
        new = []
        for n in range(state):
            h_n = jnp.exp(dt_t * a_ref[n]) * h[n] + dtx * b_ref[t, n]
            y = y + h_n * c_ref[t, n]
            new.append(h_n)
        y_ref[t] = y.astype(y_ref.dtype)
        return tuple(new)

    h = jax.lax.fori_loop(0, chunk, step,
                          tuple(h_ref[n] for n in range(state)))
    for n in range(state):
        h_ref[n] = h[n]


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref, hs_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, dbc_ref,
                g_ref, hprev_ref, rows_ref, *, chunk: int, state: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)        # the LAST chunk in time
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    # 1. the chunk's states again, h_{t-1} kept for every step
    def again(t, h):
        dt_t = dt_ref[t]
        dtx = dt_t * x_ref[t]
        new = []
        for n in range(state):
            hprev_ref[t, n] = h[n]
            new.append(jnp.exp(dt_t * a_ref[n]) * h[n] + dtx * b_ref[t, n])
        return tuple(new)

    jax.lax.fori_loop(0, chunk, again,
                      tuple(hs_ref[n] for n in range(state)))
    d = d_ref[...]

    # 2. back through the chunk.  g[n] enters a step as a_{t+1} g_{t+1},
    #    what the later steps hand to h_t.
    def back(i, carry):
        g, da, dd = carry
        t = chunk - 1 - i
        x_t, dt_t, dy_t = x_ref[t], dt_ref[t], dy_ref[t]
        dtx = dt_t * x_t
        dxb = jnp.zeros_like(x_t)          # sum_n g_n B_t[n]
        ddt = jnp.zeros_like(x_t)
        g_new, da_new = [], []
        for n in range(state):
            a_n = a_ref[n]
            decay = jnp.exp(dt_t * a_n)
            h_prev = hprev_ref[t, n]
            b_tn, c_tn = b_ref[t, n], c_ref[t, n]
            h_n = decay * h_prev + dtx * b_tn
            g_n = g[n] + dy_t * c_tn
            rows_ref[t, pl.ds(n, 1), :] = jnp.sum(
                g_n * dtx, axis=0, keepdims=True)            # -> dB_t[n]
            rows_ref[t, pl.ds(state + n, 1), :] = jnp.sum(
                dy_t * h_n, axis=0, keepdims=True)           # -> dC_t[n]
            dxb = dxb + g_n * b_tn
            g_decay = g_n * decay
            through_decay = g_decay * h_prev    # dL/d(decay) * decay
            ddt = ddt + through_decay * a_n
            da_new.append(da[n] + through_decay * dt_t)
            g_new.append(g_decay)
        dx_ref[t] = (dt_t * dxb + d * dy_t).astype(dx_ref.dtype)
        ddt_ref[t] = (x_t * dxb + ddt).astype(ddt_ref.dtype)
        return tuple(g_new), tuple(da_new), dd + dy_t * x_t

    g, da, dd = jax.lax.fori_loop(
        0, chunk, back,
        (tuple(g_ref[n] for n in range(state)),
         tuple(da_ref[n] for n in range(state)), dd_ref[...]))
    for n in range(state):
        g_ref[n] = g[n]
        da_ref[n] = da[n]
    dd_ref[...] = dd
    # 3. the chunk's rows summed over lanes: ones . rows^T on the MXU, so
    #    that (t, n) lands in the lanes of one dense row.
    rows = rows_ref[...].reshape(chunk * 2 * state, LANES)
    summed = jax.lax.dot_general(
        jnp.ones((SUBLANES, LANES), F32), rows, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)
    dbc_ref[...] = summed[0:1]


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=48 << 20)


def _blocked(x, groups: int):
    """[b, T, channels] -> [b, T, groups, 8, 128]."""
    return x.reshape(*x.shape[:2], groups, SUBLANES, LANES)


def _specs(chunk: int, state: int, time_index):
    """Block specs shared by the two kernels; `time_index(k)` is the
    chunk a grid step works on."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    seq = pl.BlockSpec((None, chunk, None, SUBLANES, LANES),
                       lambda b, g, k: (b, time_index(k), g, 0, 0))
    a = pl.BlockSpec((state, None, SUBLANES, LANES),
                     lambda b, g, k: (0, g, 0, 0))
    bc = pl.BlockSpec((None, chunk, state),
                      lambda b, g, k: (b, time_index(k), 0),
                      memory_space=pltpu.SMEM)
    d = pl.BlockSpec((None, SUBLANES, LANES), lambda b, g, k: (g, 0, 0))
    states = pl.BlockSpec((None, None, None, state, SUBLANES, LANES),
                          lambda b, g, k: (b, time_index(k), g, 0, 0, 0))
    return seq, a, bc, d, states


@functools.partial(jax.jit, static_argnums=(6, 7))
def _scan_fwd(x5, dt5, a4, B, C, d3, chunk: int, save_states: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, groups = x5.shape[:3]
    state = a4.shape[0]
    chunks = t // chunk
    seq, a, bc, d, states = _specs(chunk, state, lambda k: k)
    out_specs = [seq]
    out_shape = [jax.ShapeDtypeStruct(x5.shape, F32)]
    if save_states:
        out_specs.append(states)
        out_shape.append(jax.ShapeDtypeStruct(
            (b, chunks, groups, state, SUBLANES, LANES), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, state=state,
                          save_states=save_states),
        grid=(b, groups, chunks),
        in_specs=[seq, seq, a, bc, bc, d],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((state, SUBLANES, LANES), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="selective_scan_fwd",
    )(x5, dt5, a4, B, C, d3)
    return tuple(out) if save_states else (out[0], None)


@functools.partial(jax.jit, static_argnums=(8,))
def _scan_bwd(x5, dt5, a4, B, C, d3, dy5, hs, chunk: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, groups = x5.shape[:3]
    state = a4.shape[0]
    chunks = t // chunk
    seq, a, bc, d, states = _specs(chunk, state, lambda k: chunks - 1 - k)
    width = chunk * 2 * state
    dx5, ddt5, da, dd, dbc = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, state=state),
        grid=(b, groups, chunks),
        in_specs=[seq, seq, a, bc, bc, d, seq, states],
        out_specs=[
            seq, seq,
            # dA and dD: one block a (batch row, channel block), resident
            # over the chunks and summed over the rows outside
            pl.BlockSpec((None, None, state, SUBLANES, LANES),
                         lambda b, g, k: (b, g, 0, 0, 0)),
            pl.BlockSpec((None, None, SUBLANES, LANES),
                         lambda b, g, k: (b, g, 0, 0)),
            pl.BlockSpec((None, None, 1, width),
                         lambda b, g, k: (b, g, 0, chunks - 1 - k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x5.shape, F32),
            jax.ShapeDtypeStruct(x5.shape, F32),
            jax.ShapeDtypeStruct((b, groups, state, SUBLANES, LANES), F32),
            jax.ShapeDtypeStruct((b, groups, SUBLANES, LANES), F32),
            jax.ShapeDtypeStruct((b, groups, 1, chunks * width), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((state, SUBLANES, LANES), F32),
            pltpu.VMEM((chunk, state, SUBLANES, LANES), F32),
            pltpu.VMEM((chunk, 2 * state, LANES), F32),
        ],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="selective_scan_bwd",
    )(x5, dt5, a4, B, C, d3, dy5, hs)
    # [b, groups, 1, chunks * chunk * 2 * state] -> dB, dC [b, T, state]
    dbc = dbc.reshape(b, groups, t, 2, state).sum(axis=1)
    return (dx5, ddt5, da.sum(axis=0).transpose(1, 0, 2, 3),
            dbc[:, :, 0], dbc[:, :, 1], dd.sum(axis=0))


# ---------------------------------------------------------------------------
# custom VJP over the padded, blocked operands (all float32)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x5, dt5, a4, B, C, d3, chunk):
    return _scan_fwd(x5, dt5, a4, B, C, d3, chunk, False)[0]


def _scan_vjp_fwd(x5, dt5, a4, B, C, d3, chunk):
    y5, hs = _scan_fwd(x5, dt5, a4, B, C, d3, chunk, True)
    return y5, (x5, dt5, a4, B, C, d3, hs)


def _scan_vjp_bwd(chunk, res, dy5):
    x5, dt5, a4, B, C, d3, hs = res
    return _scan_bwd(x5, dt5, a4, B, C, d3, dy5.astype(F32), hs, chunk)


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _scan_pallas(x, dt, A, B, C, D, chunk: int):
    """Pad time to the chunk and channels to the channel block, view the
    operands as the kernels want them, and undo both on the way out (JAX
    differentiates the padding and the views)."""
    b, t, c = x.shape
    pad_t, pad_c = -t % chunk, -c % CHANNEL_BLOCK
    groups = (c + pad_c) // CHANNEL_BLOCK

    def seq(a):
        return _blocked(jnp.pad(a.astype(F32),
                                ((0, 0), (0, pad_t), (0, pad_c))), groups)

    a4 = jnp.pad(A.astype(F32), ((0, pad_c), (0, 0))).T.reshape(
        A.shape[1], groups, SUBLANES, LANES)
    d3 = jnp.pad(D.astype(F32), (0, pad_c)).reshape(groups, SUBLANES, LANES)
    B, C = (jnp.pad(a.astype(F32), ((0, 0), (0, pad_t), (0, 0)))
            for a in (B, C))
    y5 = _scan(seq(x), seq(dt), a4, B, C, d3, chunk)
    return y5.reshape(b, t + pad_t, c + pad_c)[:, :t, :c].astype(x.dtype)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

def selective_scan(x, dt, A, B, C, D, chunk: Optional[int] = None):
    """y of the recurrence in the module's header.  x, dt: [b, T, c];
    A: [c, n]; B, C: [b, T, n]; D: [c].  dt is the step AFTER softplus.

    On TPU (or interpreted, for tests) the Pallas kernels; elsewhere
    `selective_scan_xla`.  Under an ambient multi-device mesh the kernel
    runs per shard inside a shard_map, batch over the data/fsdp axes:
    GSPMD cannot partition a Mosaic kernel itself.
    """
    t = x.shape[1]
    chunk = chunk or min(DEFAULT_CHUNK, -(-t // SUBLANES) * SUBLANES)
    interpret = dispatch.interpret_mode()
    if not interpret and dispatch.platform() != "tpu":
        dispatch.record("selective_scan", "xla")
        return selective_scan_xla(x, dt, A, B, C, D, chunk)
    dispatch.record("selective_scan", "interpret" if interpret else "pallas")
    dispatch.record("selective_scan.plan",
                    f"chunk{chunk},channels{CHANNEL_BLOCK},seq{t},"
                    f"state{A.shape[1]}")

    def kernel(x, dt, B, C, A, D):
        return _scan_pallas(x, dt, A, B, C, D, chunk)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return kernel(x, dt, B, C, A, D)
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    batch = tuple(a for a in ("data", "fsdp") if a in sizes)
    if x.shape[0] % math.prod(sizes[a] for a in batch):
        batch = ()
    row = P(batch or None)
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(row, row, row, row, P(), P()),
                         out_specs=row, check_vma=False)(x, dt, B, C, A, D)
