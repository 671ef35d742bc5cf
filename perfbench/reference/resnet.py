"""Plain reference of the ResNet symbol the ``fit`` driver trains
(example/image-classification/symbols/resnet.py: pre-activation units,
BN -> ReLU -> conv, projection shortcut off the unit's first activation):
forward, softmax cross-entropy, gradients and SGD-momentum steps in
``jax.numpy``.  Imports nothing of the program.

``dtype`` is the precision everything is held and computed in: float32
(matmul precision ``highest``) is the reference, bfloat16 is the control,
the next precision below the configuration's bf16 compute over f32 masters
(it also holds parameters and momentum in bf16).  Each residual unit is
rematerialised in the backward pass so that batch 256 at 224 px fits
beside nothing else on a 16 GB chip.

Departures from the program, on purpose: batch statistics by ``jnp.var``
(two passes) and no moving averages (they do not enter a training step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 2e-5
DEPTHS = {18: ([2, 2, 2, 2], False), 34: ([3, 4, 6, 3], False),
          50: ([3, 4, 6, 3], True), 101: ([3, 4, 23, 3], True),
          152: ([3, 8, 36, 3], True)}


def unit_plan(width, stride, deep):
    if deep:
        return [(width // 4, 1, 1), (width // 4, 3, stride), (width, 1, 1)]
    return [(width, 3, stride), (width, 3, 1)]


def layer_shapes(num_layers=50, classes=1000, image=224):
    """Every parameter of the symbol, in order: ``(name, shape, kind,
    out_hw)``; kind is ``conv`` / ``bn`` / ``fc``, and ``out_hw`` the output
    height of a conv (what counts.py multiplies by)."""
    repeats, deep = DEPTHS[num_layers]
    base = 256 if deep else 64
    widths = [64] + [base << i for i in range(4)]
    out = [("input_whiten", (3,), "bn", None)]
    hw = (image + 2 * 3 - 7) // 2 + 1
    out.append(("stem_w", (widths[0], 3, 7, 7), "conv", hw))
    out.append(("stem_norm", (widths[0],), "bn", None))
    hw = (hw + 2 - 3) // 2 + 1
    cin = widths[0]
    for stage, (reps, width) in enumerate(zip(repeats, widths[1:])):
        for unit in range(reps):
            stride = 2 if (stage > 0 and unit == 0) else 1
            tag = f"s{stage}u{unit}"
            c, unit_in_hw = cin, hw
            for step, (w, k, s) in enumerate(unit_plan(width, stride, deep)):
                out.append((f"{tag}_p{step}_norm", (c,), "bn", None))
                hw = (hw + 2 * (k // 2) - k) // s + 1
                out.append((f"{tag}_p{step}_w", (w, c, k, k), "conv", hw))
                c = w
            if unit == 0:
                out.append((f"{tag}_proj", (width, cin, 1, 1), "conv",
                            (unit_in_hw - 1) // stride + 1))
            cin = width
    out.append(("head_norm", (cin,), "bn", None))
    out.append(("fc1", (classes, cin), "fc", None))
    return out


def init_params(seed, num_layers=50, classes=1000, image=224):
    """The benchmark's weights, made on the device in one jitted call from
    the seed: He-normal convolutions and classifier, BN scale 1 and shift 0.
    Names are the symbol's argument names."""
    shapes = layer_shapes(num_layers, classes, image)

    @jax.jit
    def make(key):
        p = {}
        for i, (name, shape, kind, _) in enumerate(shapes):
            if kind == "bn":
                p[name + "_gamma"] = jnp.ones(shape, jnp.float32)
                p[name + "_beta"] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = int(np.prod(shape[1:]))
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * np.sqrt(2.0 / fan_in)
                if kind == "fc":
                    p[name + "_weight"] = w
                    p[name + "_bias"] = jnp.zeros(shape[:1], jnp.float32)
                else:
                    p[name + "_weight"] = w
        return p

    return make(jax.random.PRNGKey(int(seed) % (2 ** 31)))


def _bn(x, gamma, beta, fix_gamma=False):
    xf = x.astype(jnp.float32) if x.dtype == jnp.float32 else x
    mean = jnp.mean(xf, axis=(0, 2, 3), keepdims=True)
    var = jnp.var(xf, axis=(0, 2, 3), keepdims=True)
    g = 1.0 if fix_gamma else gamma.reshape(1, -1, 1, 1)
    return (xf - mean) * jax.lax.rsqrt(var + BN_EPS) * g \
        + beta.reshape(1, -1, 1, 1)


def _conv(x, w, stride):
    k = w.shape[-1]
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _unit(x, p, tag, width, stride, project, deep):
    first, h = None, x
    for step, (_, _, s) in enumerate(unit_plan(width, stride, deep)):
        t = f"{tag}_p{step}"
        act = jax.nn.relu(_bn(h, p[t + "_norm_gamma"], p[t + "_norm_beta"]))
        if first is None:
            first = act
        h = _conv(act, p[t + "_w_weight"], s)
    skip = _conv(first, p[tag + "_proj_weight"], stride) if project else x
    return h + skip


def forward(p, x, num_layers=50):
    """Logits ``(N, classes)`` for images ``(N, 3, H, W)``."""
    repeats, deep = DEPTHS[num_layers]
    base = 256 if deep else 64
    x = _bn(x, None, p["input_whiten_beta"], fix_gamma=True)
    x = _conv(x, p["stem_w_weight"], 2)
    x = jax.nn.relu(_bn(x, p["stem_norm_gamma"], p["stem_norm_beta"]))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, reps in enumerate(repeats):
        for unit in range(reps):
            stride = 2 if (stage > 0 and unit == 0) else 1
            x = jax.checkpoint(functools.partial(
                _unit, tag=f"s{stage}u{unit}", width=base << stage,
                stride=stride, project=(unit == 0), deep=deep))(x, p)
    x = jax.nn.relu(_bn(x, p["head_norm_gamma"], p["head_norm_beta"]))
    x = jnp.mean(x, axis=(2, 3))
    return x @ p["fc1_weight"].T + p["fc1_bias"]


def loss_fn(p, x, y, num_layers=50):
    """Mean softmax cross-entropy (the program's SoftmaxOutput gradient
    times its ``rescale_grad = 1/batch``)."""
    logp = jax.nn.log_softmax(forward(p, x, num_layers).astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("num_layers", "lr", "momentum"),
                   donate_argnums=(0, 1))
def sgd_step(p, mom, x, y, *, num_layers, lr, momentum):
    """One SGD-momentum step as the program's optimizer takes it:
    ``mom = momentum * mom - lr * grad; w += mom``.  Returns the loss at the
    parameters the step started from."""
    loss, g = jax.value_and_grad(loss_fn)(p, x, y, num_layers)
    mom = {k: (momentum * mom[k] - lr * g[k]).astype(p[k].dtype) for k in p}
    p = {k: p[k] + mom[k] for k in p}
    return p, mom, loss


def follow(params, batches, *, num_layers, lr, momentum, dtype="float32"):
    """Drive ``sgd_step`` over ``batches`` (``[(x, y), ...]``) from
    ``params``.  Returns ``losses``, the first step's gradient
    (``-mom/lr`` after one step, as the program's is read) and the
    parameters' total change, each as host arrays keyed by name."""
    dt = jnp.dtype(dtype)
    prec = "highest" if dt == jnp.float32 else "default"
    p0 = {k: np.asarray(v, np.float32) for k, v in params.items()}
    p = {k: jnp.asarray(v, dt) for k, v in p0.items()}
    mom = {k: jnp.zeros_like(v) for k, v in p.items()}
    losses, grad1 = [], None
    with jax.default_matmul_precision(prec):
        for i, (x, y) in enumerate(batches):
            p, mom, loss = sgd_step(p, mom, jnp.asarray(x, dt),
                                    jnp.asarray(y, jnp.int32),
                                    num_layers=num_layers, lr=lr,
                                    momentum=momentum)
            losses.append(float(loss))
            if i == 0:
                grad1 = {k: -np.asarray(v, np.float32) / lr
                         for k, v in mom.items()}
    delta = {k: np.asarray(p[k], np.float32) - p0[k] for k in p}
    return {"losses": losses, "grad1": grad1, "delta": delta}
