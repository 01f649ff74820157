"""On-device validation harness for the flash-attention dropout kernel.

Run on a real TPU.  Checks:
1. rate=0 kernel output + analytic grads match attention_reference;
2. same-seed determinism / different-seed divergence;
3. E[dropout output] over seeds approaches the undropped output;
4. dv linearity (o is linear in v for fixed masks, so the directional
   derivative is exact up to f32 matmul noise);
5. rate->0 grad continuity to the rate=0 grads.
A finite-difference check on sum(o^2) does NOT work here: the loss is
~1e4 in f32, so central differences drown in rounding noise.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("PT_FLASH_ATTENTION", "1")

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels import attention_reference, flash_attention


def main():
    rng = np.random.RandomState(0)
    b, h, s, d = 2, 4, 512, 64
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * 0.5)
               for _ in range(3))
    C = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    seed = jnp.asarray([7.0], jnp.float32)

    o0 = flash_attention(q, k, v)
    ref = attention_reference(q, k, v, scale=1 / np.sqrt(d))
    print("rate0 out max diff:", float(jnp.max(jnp.abs(o0 - ref))))

    def l_k(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_) * C)

    def l_r(q_, k_, v_):
        return jnp.sum(attention_reference(q_, k_, v_,
                                           scale=1 / np.sqrt(d)) * C)

    gk = jax.grad(l_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(l_r, argnums=(0, 1, 2))(q, k, v)
    for i, nm in enumerate("qkv"):
        rel = float(jnp.linalg.norm(gk[i] - gr[i])
                    / (jnp.linalg.norm(gr[i]) + 1e-9))
        print(f"rate0 d{nm} rel err vs reference: {rel:.5f}")
        assert rel < 5e-3, rel

    f = jax.jit(lambda sd: flash_attention(q, k, v, dropout_rate=0.1,
                                           dropout_seed=sd))
    assert float(jnp.max(jnp.abs(f(seed) - f(seed)))) == 0.0
    assert float(jnp.max(jnp.abs(
        f(seed) - f(jnp.asarray([8.0], jnp.float32))))) > 0
    print("determinism: ok")

    outs = [f(jnp.asarray([float(i)], jnp.float32)) for i in range(24)]
    rel = float(jnp.linalg.norm(jnp.mean(jnp.stack(outs), 0) - o0)
                / jnp.linalg.norm(o0))
    print(f"E[dropout out] rel err vs undropped: {rel:.4f}")
    assert rel < 0.15

    def fv(v_):
        return jnp.sum(flash_attention(q, k, v_, dropout_rate=0.1,
                                       dropout_seed=seed) * C)

    dv = jax.grad(fv)(v)
    dvec = jnp.asarray(np.random.RandomState(5).randn(*v.shape)
                       .astype(np.float32))
    dvec /= jnp.linalg.norm(dvec)
    num = (fv(v + dvec) - fv(v - dvec)) / 2.0
    ana = jnp.sum(dv * dvec)
    print(f"dv linearity: analytic {float(ana):.5f} numeric {float(num):.5f}")
    assert abs(float(ana) - float(num)) < 0.05 * max(1e-3, abs(float(num)))

    g_small = jax.grad(lambda q_, k_, v_: jnp.sum(flash_attention(
        q_, k_, v_, dropout_rate=1e-6, dropout_seed=seed) * C),
        argnums=(0, 1))(q, k, v)
    for i, nm in enumerate("qk"):
        rel = float(jnp.linalg.norm(g_small[i] - gr[i])
                    / (jnp.linalg.norm(gr[i]) + 1e-9))
        print(f"rate->0 d{nm} rel err vs rate0: {rel:.5f}")
        assert rel < 5e-3
    print("ALL OK")


if __name__ == "__main__":
    main()
