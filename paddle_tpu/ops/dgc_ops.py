"""DGC — Deep Gradient Compression ops.

Reference: the DGC external lib (cmake/external/dgc.cmake), dgc_op.cc /
dgc_momentum_op.cc, and details/sparse_all_reduce_op_handle.cc (top-k
sparse allreduce over NCCL).  Capability: communicate only the top-k
largest accumulated-gradient entries per step, with momentum correction
and local gradient accumulation (Lin et al., "Deep Gradient
Compression").

TPU-native shape: one fused ``dgc`` op does the whole per-parameter
step — momentum correction, top-k selection, sparse exchange, residual
update — keeping every shape static for XLA:

  u = m * u + g                      (momentum correction)
  v = v + u                          (local accumulation)
  idx = top-k(|v|)                   (k = ratio * numel, static)
  exchange (v[idx], idx)             (all_gather over the mesh axis --
                                      2*k*nranks elements instead of
                                      numel: that's the compression)
  agg = scatter-add of all ranks' sparse entries / nranks
  u[idx] = 0 ; v[idx] = 0            (residual: unsent grads accumulate)

Rampup (reference dgc ramps sparsity 75%→99.9% over rampup_step steps)
is expressed with a static k_max = k(first ramp sparsity) and a traced
effective-k mask, so the program never changes shape; with the default
single-value schedule [0.999] k_max is already the final k.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import op
from .collective_ops import _axis, _in_shard_map


def _effective_k(step, numel, sparsity, rampup_begin, rampup_step, k_max):
    """Traced effective k for the current step (<= static k_max)."""
    n_stages = len(sparsity)
    if n_stages == 1 or rampup_step <= 0:
        return jnp.full((), k_max, jnp.int32)
    per = max(1, rampup_step // n_stages)
    stage = jnp.clip((step - rampup_begin) // per, 0, n_stages - 1)
    ks = jnp.asarray(
        [max(1, int(round(numel * (1.0 - s)))) for s in sparsity],
        jnp.int32)
    return jnp.minimum(ks[stage], k_max)


@op("dgc", no_grad=True)
def _dgc(ctx):
    """Fused DGC step.  Inputs: U, V, Grad, current_step.  Outputs:
    U_out, V_out, Grad_out (the aggregated dense gradient, averaged
    over ranks), EncodeGrad (sent values), GatherBuff (sent indices)."""
    u = jnp.asarray(ctx.in_("U"))
    v = jnp.asarray(ctx.in_("V"))
    g = jnp.asarray(ctx.in_("Grad"))
    step = jnp.asarray(ctx.in_("current_step")).astype(jnp.int32).reshape(())

    m = ctx.attr("m", 0.9)
    use_nesterov = ctx.attr("use_nesterov", False)
    sparsity = list(ctx.attr("sparsity", [0.999]))
    rampup_begin = int(ctx.attr("rampup_begin_step", 0))
    rampup_step = int(ctx.attr("rampup_step", 0))

    shape = jnp.shape(g)
    numel = int(np.prod(shape))
    k_max = max(1, int(round(numel * (1.0 - float(min(sparsity))))))

    u_prev, v_prev = u, v
    u = m * u + g
    if use_nesterov:
        acc = g + m * u
    else:
        acc = u
    v = v + acc

    flat_v = jnp.reshape(v, (numel,))
    _, idx = lax.top_k(jnp.abs(flat_v), k_max)
    vals = jnp.take(flat_v, idx)

    # rampup: mask out entries beyond the step's effective k
    eff_k = _effective_k(step, numel, sparsity, rampup_begin, rampup_step,
                         k_max)
    keep = (jnp.arange(k_max, dtype=jnp.int32) < eff_k)
    vals = jnp.where(keep, vals, 0.0)
    # masked-out entries must NOT be cleared from the residual
    clear_idx = jnp.where(keep, idx, numel)  # out-of-range -> dropped

    axis = _axis(ctx)
    if _in_shard_map(axis):
        all_vals = lax.all_gather(vals, axis)      # [nranks, k]
        all_idx = lax.all_gather(idx, axis)
        nranks = all_vals.shape[0]
        agg = jnp.zeros((numel,), flat_v.dtype)
        agg = agg.at[jnp.reshape(all_idx, (-1,))].add(
            jnp.reshape(all_vals, (-1,)))
        agg = agg / nranks
    else:
        agg = jnp.zeros((numel,), flat_v.dtype).at[idx].add(vals)

    # residual update (scatter with a drop-out-of-range guard)
    flat_u = jnp.reshape(u, (numel,))
    flat_u = flat_u.at[clear_idx].set(0.0, mode="drop")
    flat_v = flat_v.at[clear_idx].set(0.0, mode="drop")
    u_out = jnp.reshape(flat_u, shape)
    v_out = jnp.reshape(flat_v, shape)
    agg_out = jnp.reshape(agg, shape)

    if rampup_begin > 0:
        # pre-rampup dense passthrough (reference: dgc_op.cc copies the
        # grad through before rampup_begin_step; dgc_momentum applies
        # classic momentum then).  Both exchanges exist in the compiled
        # program, where-gated on the step — programs compiled with
        # rampup_begin_step == 0 carry no dense path at all.
        pre = step < jnp.int32(rampup_begin)
        if _in_shard_map(axis):
            dense = lax.psum(jnp.where(pre, g, jnp.zeros_like(g)), axis)
            dense = dense / lax.axis_size(axis)
        else:
            dense = g
        u_out = jnp.where(pre, u_prev, u_out)
        v_out = jnp.where(pre, v_prev, v_out)
        agg_out = jnp.where(pre, dense, agg_out)

    ctx.set_out("U_out", u_out)
    ctx.set_out("V_out", v_out)
    ctx.set_out("Grad_out", agg_out)
    ctx.set_out("EncodeGrad", vals)
    ctx.set_out("GatherBuff", idx.astype(jnp.int32))


@op("dgc_momentum", no_grad=True)
def _dgc_momentum(ctx):
    """reference: dgc_momentum_op.cc — momentum update that switches to
    plain SGD once DGC is active (the momentum lives in U then).
    Inputs: Param, Grad, Velocity, LearningRate, current_step."""
    p = ctx.in_("Param")
    g = ctx.in_("Grad")
    vel = ctx.in_("Velocity")
    lr = jnp.asarray(ctx.in_("LearningRate")).reshape(())
    step = jnp.asarray(ctx.in_("current_step")).astype(jnp.int32).reshape(())
    mu = ctx.attr("mu", 0.9)
    rampup_begin = int(ctx.attr("rampup_begin_step", 0))
    use_nesterov = ctx.attr("use_nesterov", False)

    # before rampup_begin: classic momentum; after: sgd (momentum is
    # applied inside the dgc op's U buffer)
    new_vel = mu * vel + g
    if use_nesterov:
        mom_update = p - lr * (g + mu * new_vel)
    else:
        mom_update = p - lr * new_vel
    sgd_update = p - lr * g

    use_momentum = step < rampup_begin
    ctx.set_out("ParamOut", jnp.where(use_momentum, mom_update, sgd_update))
    ctx.set_out("VelocityOut",
                jnp.where(use_momentum, new_vel, jnp.zeros_like(new_vel)))
