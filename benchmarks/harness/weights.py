"""Seeded weights for the configurations, made on the device in one
jitted call, as f32 master copies (what ``FusedPallasBackend.program``
stages; the precision policy rounds them at solve time).

Two schemes, named by a configuration's ``weights.scheme``:

``he``
    He-normal weights, zero biases (the program's ``mlp_init`` rule).

``bounded_oscillator``
    dy/dt = -y + k·Q·clip(y, -a, a) + V·g(y), written exactly as a
    ReLU MLP of the configuration's widths.  ``Q = c·I + R`` with ``R``
    three 2-D rotations, so the origin is an unstable spiral
    (k·c > 1) and the clip bounds the growth; ``g`` is a layer of random
    units fed by clipped random features, so it is bounded too.  Hence
    |y| stays below about ``k·|Q|·a + |V|·max g`` whatever the horizon,
    while every twin keeps circulating.  He-init fields are positively
    homogeneous, so their states grow or decay exponentially over the
    ~1e5 steps a twin advances in one serving window.
"""
from __future__ import annotations

import numpy as np


def make_weights(config: dict, jax_seed: int):
    import jax
    sizes = layer_sizes(config)
    scheme = config["weights"]["scheme"]
    if scheme == "he":
        fn = lambda key: he_init(key, sizes)
    elif scheme == "bounded_oscillator":
        fn = lambda key: bounded_oscillator(key, sizes, **{
            k: v for k, v in config["weights"].items() if k != "scheme"})
    else:
        raise ValueError(f"unknown weights scheme {scheme!r}")
    return jax.jit(fn)(jax.random.PRNGKey(jax_seed))


def layer_sizes(config: dict) -> tuple:
    d, h = config["state_dim"], config["hidden"]
    return ((config.get("drive_dim", 0) + d,) + (h,) * config["n_hidden_layers"]
            + (d,))


def he_init(key, sizes):
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(key, len(sizes) - 1)
    return [{"w": jax.random.normal(k, (i, o), jnp.float32) * jnp.sqrt(2.0 / i),
             "b": jnp.zeros((o,), jnp.float32)}
            for k, i, o in zip(keys, sizes[:-1], sizes[1:])]


def bounded_oscillator(key, sizes, *, clip: float, gain: float,
                       growth: float, omegas, scale: float):
    import jax
    import jax.numpy as jnp
    D, H, H2, Do = sizes
    if not (D == Do and H == H2 and H > 4 * D and (H - 4 * D) % 2 == 0
            and len(omegas) * 2 == D):
        raise ValueError(f"bounded_oscillator needs D -> H -> H -> D with "
                         f"H > 4D and D/2 rotation rates, got {sizes}")
    a, k = float(clip), float(gain)
    ks = jax.random.split(key, 5)
    eye = jnp.eye(D, dtype=jnp.float32)
    P = (H - 4 * D) // 2                       # random clipped feature pairs
    r = 4 * D                                  # first random unit
    # layer 0: relu(y), relu(-y) (the -y path), relu(y+a), relu(y-a)
    # (clip(y) = relu(y+a) - relu(y-a) - a), then pairs (z, z-a) of random
    # features z = w.y + b, whose difference is clip(z, 0, a)
    w0 = jnp.zeros((D, H), jnp.float32)
    w0 = (w0.at[:, 0:D].set(eye).at[:, D:2 * D].set(-eye)
          .at[:, 2 * D:3 * D].set(eye).at[:, 3 * D:r].set(eye))
    wz = jax.random.normal(ks[0], (D, P)) * scale / np.sqrt(D)
    bz = jax.random.uniform(ks[1], (P,), minval=-1.0, maxval=1.0)
    w0 = w0.at[:, r::2].set(wz).at[:, r + 1::2].set(wz)
    b0 = (jnp.zeros((H,), jnp.float32).at[2 * D:3 * D].set(a)
          .at[3 * D:r].set(-a).at[r::2].set(bz).at[r + 1::2].set(bz - a))
    # layer 1: pass the first 4D units through (they are >= 0), and random
    # units g = relu(M clip(z, 0, a) + c)
    w1 = jnp.zeros((H, H), jnp.float32).at[jnp.arange(r), jnp.arange(r)].set(1.0)
    m = jax.random.normal(ks[2], (P, H - r)) * scale / np.sqrt(P)
    w1 = w1.at[r::2, r:].set(m).at[r + 1::2, r:].set(-m)
    b1 = jnp.zeros((H,), jnp.float32).at[r:].set(
        0.5 * jax.random.normal(ks[3], (H - r,)))
    # layer 2: -y + k Q clip(y) + V g
    q = growth * np.eye(D)
    for i, om in enumerate(omegas):
        q[2 * i, 2 * i + 1], q[2 * i + 1, 2 * i] = om / k, -om / k
    qt = jnp.asarray(q.T, jnp.float32)
    w2 = (jnp.zeros((H, D), jnp.float32).at[0:D].set(-eye).at[D:2 * D].set(eye)
          .at[2 * D:3 * D].set(k * qt).at[3 * D:r].set(-k * qt))
    w2 = w2.at[r:].set(jax.random.normal(ks[4], (H - r, D)) * scale
                       / np.sqrt(H - r))
    b2 = -k * a * jnp.sum(qt, axis=0)
    return [{"w": w0, "b": b0}, {"w": w1, "b": b1}, {"w": w2, "b": b2}]
