"""The plain reference of a training cell: loss, gradients and AdamW in
float32 (``highest``), layer by layer so that it fits one chip beside
nothing else.  Nothing is imported from the program.

What it follows is what the configuration states: parameters stored in the
configuration's dtype (bf16: each step's new parameters are rounded to it,
the arithmetic of the step itself is float32), AdamW with decoupled weight
decay, the cosine schedule, the mean next-token cross entropy over all of
the batch's tokens.

Departures, each forced by 16 GB: the two moments are held between steps in
``moment_dtype`` (bf16 at the 7B widths: a relative rounding of 2**-9 with
random sign per element, which moves a leaf's norm by under 1e-4 of itself;
float32 in the tests); activations at the layers' boundaries wait on the
host between the forward and the backward pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W
from benchmark.reference import llama_ref as R

B1, B2, EPS = 0.9, 0.95, 1e-8        # the LLaMA recipe (make_optimizer)


def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up from 0 over ``warmup_steps``, then cosine decay to a
    tenth of the peak at ``decay_steps`` (optax's
    ``warmup_cosine_decay_schedule``)."""
    peak, warm = opt["learning_rate"], opt["warmup_steps"]
    if count < warm:
        return peak * count / warm
    span = max(opt["decay_steps"], warm + 1) - warm
    frac = min(count - warm, span) / span
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    return peak * ((1 - 0.1) * cos + 0.1)


def adamw(theta, m, v, g, count, lr, wd):
    """One AdamW update of one leaf; returns the new (theta, m, v) in the
    dtypes they are stored in."""
    m32 = B1 * m.astype(jnp.float32) + (1 - B1) * g
    v32 = B2 * v.astype(jnp.float32) + (1 - B2) * g * g
    t = count + 1
    upd = (m32 / (1 - B1 ** t)) / (jnp.sqrt(v32 / (1 - B2 ** t)) + EPS)
    th32 = theta.astype(jnp.float32)
    new = th32 - lr * (upd + wd * th32)
    return new.astype(theta.dtype), m32.astype(m.dtype), v32.astype(v.dtype)


class Reference:
    def __init__(self, cfg: dict, seed: int, *, precision: str = "f32",
                 moment_dtype: str = "float32") -> None:
        self.cfg = R.with_head_dim(cfg)
        self.opt = cfg["train"]["optimizer"]
        self.precision = precision
        self.key = W.root_key(seed)
        self.store = jnp.dtype(cfg["torch_dtype"])
        self.n_layers = cfg["num_hidden_layers"]
        mdt = jnp.dtype(moment_dtype)
        c = self.cfg

        # the seed's key is an argument of every compiled piece, never a
        # constant inside one (that program would compile anew for each seed)
        @jax.jit
        def init(key):
            layers = {n: jax.lax.map(
                lambda l: W.make_leaf(key, "layers/" + n, f(c),
                                      self.store, l),
                jnp.arange(self.n_layers)) for n, f in R.LAYER_LEAVES.items()}
            top = {n: W.make_leaf(key, n, f(c), self.store)
                   for n, f in R.TOP_LEAVES.items()}
            return layers, top

        self.layers, self.top = init(self.key)
        zeros = lambda t: jax.tree.map(lambda a: jnp.zeros(a.shape, mdt), t)
        self.m = (zeros(self.layers), zeros(self.top))
        self.v = (zeros(self.layers), zeros(self.top))
        self.count = 0
        self.grad_norms: dict = {}

    # -- jitted pieces, each compiled once (the layer index is an argument) --

    @functools.cached_property
    def _fwd(self):
        cfg, prec = self.cfg, self.precision

        @jax.jit
        def fwd(layers, l, x):
            w = {n: a[l].astype(jnp.float32) for n, a in layers.items()}
            return jax.lax.map(lambda xi: R.layer(cfg, w, xi, prec), x)

        return fwd

    @functools.cached_property
    def _bwd(self):
        cfg, prec = self.cfg, self.precision

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def bwd(layers, m, v, l, x, dy, count, lr, wd):
            w = {n: a[l].astype(jnp.float32) for n, a in layers.items()}

            def row(acc, xd):
                xi, di = xd
                _, vjp = jax.vjp(jax.checkpoint(
                    lambda ww, xx: R.layer(cfg, ww, xx, prec)), w, xi)
                dw, dx = vjp(di)
                return jax.tree.map(jnp.add, acc, dw), dx

            dw, dx = jax.lax.scan(row, jax.tree.map(jnp.zeros_like, w),
                                  (x, dy))
            norms = {n: jnp.sqrt(jnp.sum(g * g)) for n, g in dw.items()}
            for n, g in dw.items():
                th, mm, vv = adamw(layers[n][l], m[n][l], v[n][l], g, count,
                                   lr, wd)
                layers[n] = layers[n].at[l].set(th)
                m[n] = m[n].at[l].set(mm)
                v[n] = v[n].at[l].set(vv)
            return layers, m, v, dx, norms

        return bwd

    @functools.cached_property
    def _head(self):
        cfg, prec = self.cfg, self.precision

        @jax.jit
        def head(top, x, targets, weight):
            fs = top["final_norm/scale"].astype(jnp.float32)
            lm = top["lm_head/kernel"].astype(jnp.float32)

            def loss_of(fs, lm, xi, ti):
                logits = R.head(cfg, fs, lm, xi, prec)
                logp = jax.nn.log_softmax(logits, -1)
                return -jnp.sum(jnp.take_along_axis(
                    logp, ti[:, None], -1)) * weight

            def row(acc, xt):
                xi, ti = xt
                loss, (dfs, dlm, dx) = jax.value_and_grad(
                    loss_of, argnums=(0, 1, 2))(fs, lm, xi, ti)
                return (acc[0] + loss, acc[1] + dfs, acc[2] + dlm), dx

            (loss, dfs, dlm), dx = jax.lax.scan(
                row, (jnp.float32(0), jnp.zeros_like(fs),
                      jnp.zeros_like(lm)), (x, targets))
            return loss, dx, {"final_norm/scale": dfs, "lm_head/kernel": dlm}

        return head

    @functools.cached_property
    def _update_top(self):
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def update(theta, m, v, g, count, lr, wd):
            return adamw(theta, m, v, g, count, lr, wd)

        return update

    @functools.cached_property
    def _embed_grad(self):
        @jax.jit
        def embed_grad(tokens, dx, like):
            return jnp.zeros(like.shape, jnp.float32).at[tokens].add(dx)

        return embed_grad

    # -----------------------------------------------------------------------

    def step(self, tokens: np.ndarray, *, update: bool = True) -> float:
        """One step on ``tokens [B, S+1]``; returns its loss.  With
        ``update=False`` only the forward pass runs (a step's loss needs
        nothing more)."""
        inputs = jnp.asarray(tokens[:, :-1])
        targets = jnp.asarray(tokens[:, 1:])
        weight = 1.0 / float(inputs.size)
        x = self.top["tok_embed/embedding"][inputs].astype(jnp.float32)
        boundaries = []
        for l in range(self.n_layers):
            if update:
                boundaries.append(np.asarray(x))        # waits on the host
            x = self._fwd(self.layers, jnp.int32(l), x)
        loss, dx, dtop = self._head(self.top, x, targets,
                                    jnp.float32(weight))
        if not update:
            return float(loss)
        lr = jnp.float32(learning_rate(self.opt, self.count))
        wd = jnp.float32(self.opt["weight_decay"])
        count = jnp.float32(self.count)
        norms = {n: jnp.sqrt(jnp.sum(g * g)) for n, g in dtop.items()}
        (ml, mt), (vl, vt) = self.m, self.v
        # the head's leaves first: their gradients are then out of the way
        for n in list(dtop):
            self.top[n], mt[n], vt[n] = self._update_top(
                self.top[n], mt[n], vt[n], dtop.pop(n), count, lr, wd)
        for l in reversed(range(self.n_layers)):
            self.layers, ml, vl, dx, ln = self._bwd(
                self.layers, ml, vl, jnp.int32(l),
                jnp.asarray(boundaries.pop()), dx, count, lr, wd)
            for n, g in ln.items():
                norms.setdefault("layers/" + n, []).append(g)
        n = "tok_embed/embedding"
        g = self._embed_grad(inputs, dx, self.top[n])
        norms[n] = jnp.sqrt(jnp.sum(g * g))
        self.top[n], mt[n], vt[n] = self._update_top(
            self.top[n], mt[n], vt[n], g, count, lr, wd)
        self.m, self.v = (ml, mt), (vl, vt)
        if self.count == 0:
            self.grad_norms = {
                n: float(jnp.sqrt(sum(x * x for x in g)))
                if isinstance(g, list) else float(g)
                for n, g in norms.items()}
        self.count += 1
        return float(loss)

    def change_norms(self) -> dict:
        """Per leaf, the norm of (parameters now - parameters at the start),
        the start made again from the seed."""
        c, store = self.cfg, self.store

        @jax.jit
        def norms(key, layers, top):
            out = {}
            for n, arr in layers.items():
                def one(l, n=n, arr=arr):
                    d = arr[l].astype(jnp.float32) - W.make_leaf(
                        key, "layers/" + n, arr.shape[1:], store,
                        l).astype(jnp.float32)
                    return jnp.sum(d * d)
                out["layers/" + n] = jnp.sqrt(jnp.sum(
                    jax.lax.map(one, jnp.arange(arr.shape[0]))))
            for n, a in top.items():
                d = a.astype(jnp.float32) - W.make_leaf(
                    key, n, R.TOP_LEAVES[n](c), store).astype(jnp.float32)
                out[n] = jnp.sqrt(jnp.sum(d * d))
            return out

        return {n: float(x) for n, x in
                norms(self.key, self.layers, self.top).items()}
