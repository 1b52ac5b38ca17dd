"""Plain reference of a dense decoder with an untied output head (Granite),
for QAT over several devices.

The blocks, quantizers, loss and AdamW are `dense_decoder`'s, imported from
it unchanged (its docstring gives the equations and the departures each
configuration lists under `assumed`). What this file adds:

  * The head is a weight of its own, "lm_head.w" (hidden, padded
    vocabulary), fake-quantized as an edge layer (`edge_bits`, signed, its
    own scale "lm_head.w_scale", the module-wise factor of its own L1); its
    input quantizer takes that factor from the head's weight too. The
    padded columns past `vocab_size` enter the L1 sums, as they are part of
    the weight, and no logit.
  * Placement. Every leaf is split over all the devices given, along its
    largest axis that their number divides, and replicated where none does
    (`shardings`). The rule is this file's own, not the program's
    (`repro.dist.sharding`), so the reference stays independent of the code
    under test; XLA partitions the plain computation around it. Every
    product is computed at the highest precision.
  * Readings reduced on the devices. What reaches the host is per-leaf
    norms, losses, and for a batch's rows the n x n Gram matrix of their
    gradients and their n projections onto a given gradient
    (`row_gram`), never a gradient: at 3 B parameters one is 12 GB.
    `row_gram` holds at most two row gradients at a time and computes the
    Gram's off-diagonal rows again as it needs them (n + n(n-1)/2
    gradients of one row).

Parameters come in the benchmark's flat layout ("embed.w",
"layers.<linear>.<leaf>" stacked over layers, "lm_head.w", ...).
`Reference(make_params, c, devices)` takes a function of no arguments that
returns them (float32), traceable: each phase makes them anew on the
devices, so that only one copy is held while stepping.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.harness.spec import load_module

D = load_module("reference", "dense_decoder")
F32 = jnp.float32
AXIS = "all"
# For programs that make the weights from the seed (here and in the mesh
# loop): XLA folds the sharded random counters into constants as large as
# the arrays while it compiles. At 3 B parameters for a described v5e:2x2
# that takes 52 s; unfolded, the weights take 38 s and the norms of a
# change from them 9 s.
NO_FOLD = {"xla_disable_hlo_passes": "constant_folding"}


def qat_loss(params: dict, batch: dict, c: dict, prec: str):
    """Sparse top-K distillation loss of the fake-quantized model with an
    untied head: the mean over the rows, or with `batch["row_w"]` their
    mean weighted so (a row of weight 0 is left out)."""
    q, eb = c["quant"], c["quant"]["edge_bits"]
    cc = dict(c, _kv_round=lambda t: t)
    lin = lambda p, n, x, eq: D._qat_linear(p, n, x, eq, q, prec)
    ew, es = params["embed.w"], params["embed.w_scale"]
    embed_q = D.fake_quant(ew, es, eb, True,
                           D._l1_factor(ew, es.shape, D._levels(eb, True)[1]))
    tokens = batch["tokens"]
    x = D._lo(embed_q[tokens] * jnp.sqrt(F32(c["hidden_size"])), prec)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    body = jax.checkpoint(lambda x, p: (D._block(x, p, pos, cc, lin, prec),
                                        None))
    x, _ = jax.lax.scan(body, x, D._layers(params))
    x = D._lo(D._rms(x, params["final_norm.g"]), prec)
    hw, hs = params["lm_head.w"], params["lm_head.w_scale"]
    head = D.fake_quant(hw, hs, eb, True, D._l1_factor(
        hw, hs.shape, D._levels(eb, True)[1]))[:, :c["vocab_size"]]
    xq = D.fake_quant(x, params["lm_head.a_scale"], eb, False,
                      D._l1_factor(hw, (), D._levels(eb, False)[1]),
                      offset=params["lm_head.a_offset"])

    @jax.checkpoint
    def row_loss(args):   # one row at a time: its logits are (S, V)
        xr, idx, p = args
        logits = D._mm("sd,dv->sv", xr, head, prec, f32_out=True)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, idx, axis=-1)
        return -jnp.mean(jnp.sum(p * picked, axis=-1))

    rows = jax.lax.map(row_loss, (xq, batch["kd_idx"], batch["kd_p"]))
    if "row_w" not in batch:
        return jnp.mean(rows)
    return jnp.sum(rows * batch["row_w"]) / jnp.sum(batch["row_w"])


def shardings(shapes: dict, devices) -> dict:
    """Per leaf: split along its largest axis that len(devices) divides
    (the first such axis on a tie), else replicated."""
    n = len(devices)
    mesh = Mesh(np.array(devices), (AXIS,))

    def spec(shape):
        axes = [i for i, s in enumerate(shape) if n > 1 and s % n == 0]
        if not axes:
            return P()
        at = max(axes, key=lambda i: (shape[i], -i))
        return P(*[AXIS if i == at else None for i in range(len(shape))])

    return {k: NamedSharding(mesh, spec(tuple(s))) for k, s in shapes.items()}


def _replicated(devices):
    return NamedSharding(Mesh(np.array(devices), (AXIS,)), P())


def _highest(f, *args):
    with jax.default_matmul_precision("highest"):
        return f(*args)


def _rows(b: dict, w=None) -> dict:
    """`b` with its row weights: `w`, or 1 for every row. Every batch
    carries them, so that one program serves a whole batch, a row and a
    batch with rows left out."""
    n = b["tokens"].shape[0]
    return dict(b, row_w=jnp.ones(n, F32) if w is None else jnp.asarray(
        w, F32))


def _row(b: dict, r: int) -> dict:
    return _rows({k: v[r:r + 1] for k, v in b.items() if k != "row_w"})


class Reference:
    """The reference of one configuration from one set of initial weights,
    over `devices`. Its jitted pieces are built once: the gradient once per
    arithmetic (`prec`), the AdamW step once per master precision."""

    def __init__(self, make_params, c: dict, devices):
        self.c = c
        t = c["train"]
        shapes = {k: v.shape for k, v in jax.eval_shape(make_params).items()}
        sh = self.sh = shardings(shapes, devices)
        one = self.one = _replicated(devices)
        norms = lambda tree: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                              for k, v in tree.items()}
        self.params = jax.jit(lambda: {k: v.astype(F32) for k, v in
                                       make_params().items()},
                              out_shardings=sh, compiler_options=NO_FOLD)
        self.to = {dt: jax.jit(lambda p, dt=dt: {
            k: v.astype(dt) for k, v in p.items()}, in_shardings=(sh,),
            out_shardings=sh, donate_argnums=0) for dt in (jnp.bfloat16, F32)}
        self.zeros = jax.jit(lambda: {k: jnp.zeros(s, F32)
                                      for k, s in shapes.items()},
                             out_shardings=sh)
        self.change = jax.jit(lambda p: norms({
            k: v - make_params()[k].astype(F32) for k, v in p.items()}),
            in_shardings=(sh,), out_shardings=one, compiler_options=NO_FOLD)
        self._place = jax.jit(lambda t: t, out_shardings=sh,
                              donate_argnums=0)
        self.dots = jax.jit(lambda a, b: {k: jnp.sum(a[k] * b[k]) for k in a},
                            in_shardings=(sh, sh), out_shardings=one)

        def clipped(g):
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
            k = jnp.minimum(1.0, t["clip_norm"] / jnp.maximum(gn, 1e-9))
            return {name: x * k for name, x in g.items()}

        self.clipped = jax.jit(clipped, in_shardings=(sh,), out_shardings=sh,
                               donate_argnums=0)
        self._grad: dict = {}
        self._step: dict = {}

    def grad(self, prec: str):
        """jit(params, batch) -> (loss, gradient) in arithmetic `prec`."""
        if prec not in self._grad:
            c = self.c
            self._grad[prec] = jax.jit(
                lambda p, b: _highest(jax.value_and_grad(
                    lambda p, b: qat_loss(p, b, c, prec)), p, b),
                in_shardings=(self.sh, None), out_shardings=(self.one, self.sh))
        return self._grad[prec]

    def step(self, master: str):
        """jit(p, g, mu, nu, i) -> (p, mu, nu, used): AdamW, the parameters
        held in `master` precision; p, mu and nu are donated."""
        if master not in self._step:
            t, sh = self.c["train"], self.sh
            self._step[master] = jax.jit(
                lambda p, g, m, v, i: D.adamw_step(p, g, m, v, i, t, master),
                in_shardings=(sh, sh, sh, sh, None),
                out_shardings=(sh, sh, sh, self.one), donate_argnums=(0, 2, 3))
        return self._step[master]

    def _held(self, p: dict, master: str) -> dict:
        """`p` as the master copy holds it. With "bf16" it is stored in a
        bfloat16 array between two programs and widened again, so that the
        rounding is a stored value, not a conversion a compiler could fold
        away."""
        return self.to[F32](self.to[jnp.bfloat16](p)) if master == "bf16" \
            else p

    def place(self, target: dict) -> dict:
        """`target` (a flat dict of device arrays with every leaf) moved onto
        this reference's placement on the devices; the dict is left empty.
        An identity program moves it where both order the devices alike
        (`jax.device_put` would send it through the host when the shards
        differ); `jax.device_put` moves it leaf by leaf, reordering on the
        devices, where they are ordered otherwise (the program's mesh)."""
        try:
            placed = self._place(dict(target))
        except ValueError:   # devices ordered otherwise: no identity program
            placed = {}
            while target:
                k, v = target.popitem()
                placed[k] = jax.device_put(v, self.sh[k])
                del v
        target.clear()
        return placed

    def _dot(self, a: dict, b: dict) -> float:
        """Sum over leaves of <a, b>: each leaf's on the devices in
        float32, the leaves summed on the host in float64."""
        return float(sum(np.float64(v) for v in
                         jax.device_get(self.dots(a, b)).values()))

    def first_gradient(self, batch: dict, prec: str = "f32",
                       master: str = "f32", row_w=None) -> dict:
        """The first step's gradient as the update uses it (after
        clipping), on the devices. `row_w` weighs the rows (0 leaves one
        out)."""
        p = self._held(self.params(), master)
        _, g = self.grad(prec)(p, _rows(batch, row_w))
        return self.clipped(g)

    def row_gram(self, batch: dict, target: dict, gram=None):
        """(gram, proj): the Gram matrix of the float32 first gradients of
        each row of `batch` alone, and each one's projection onto `target`.
        With `gram` given, only the projections are computed (n row
        gradients).

        `target` (a flat dict of device arrays with every leaf, placed any
        way) is consumed (`place`) before the parameters are made."""
        grad = self.grad("f32")
        placed = self.place(target)
        p = self.params()
        n = int(batch["tokens"].shape[0])
        want = gram is None
        gram = np.zeros((n, n)) if want else np.array(gram, np.float64)
        proj = np.zeros(n)
        for i in range(n):
            _, ri = grad(p, _row(batch, i))
            proj[i] = self._dot(ri, placed)
            if want:
                gram[i, i] = self._dot(ri, ri)
                for j in range(i + 1, n):
                    _, rj = grad(p, _row(batch, j))
                    gram[i, j] = gram[j, i] = self._dot(ri, rj)
                    del rj
            del ri
        return gram, proj

    def step_readings(self, batches: list, prec: str = "f32",
                      master: str = "f32", row_w=None) -> dict:
        """Follow the first len(batches) AdamW steps from `make_params()`.
        `master` "bf16" holds the parameters in bfloat16 (the control of a
        float32 master copy); `row_w` weighs each batch's rows. Returns, on
        the host:
          losses  the loss of each step
          grad    per leaf, the norm of the first gradient as the update
                  used it (after clipping)
          change  per leaf, the norm of the parameters' change over the
                  steps, from the float32 weights `make_params` gives"""
        grad, step = self.grad(prec), self.step(master)
        p = self._held(self.params(), master)
        mu, nu = self.zeros(), self.zeros()
        out = {"losses": []}
        for i, b in enumerate(batches):
            loss, g = grad(p, _rows(b, row_w))
            p, mu, nu, used = step(p, g, mu, nu, jnp.int32(i))
            p = self._held(p, master)
            del g
            out["losses"].append(float(loss))
            if i == 0:
                out["grad"] = {k: float(v) for k, v in
                               jax.device_get(used).items()}
        del mu, nu
        out["change"] = {k: float(v) for k, v in
                         jax.device_get(self.change(p)).items()}
        return out


def row_balance(gram, proj) -> float:
    """`compare.row_balance` from the Gram matrix of the rows' gradients and
    their projections onto the gradient under test: that gradient fitted
    as a weighted sum of the rows' (least squares), the weights scaled to a
    mean of 1, the largest departure of one from 1."""
    a = np.linalg.solve(np.asarray(gram, np.float64),
                        np.asarray(proj, np.float64))
    if not np.all(np.isfinite(a)) or a.mean() <= 0:
        return float("inf")
    return float(np.max(np.abs(a / a.mean() - 1.0)))
