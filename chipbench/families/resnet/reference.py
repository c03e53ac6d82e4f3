"""Plain float32 reference for the bottleneck-ResNet family (ResNet-50,
Wide-ResNet-50-2): initialisation from the seed, forward pass with
BatchNorm's batch statistics, softmax cross-entropy, backward pass and
the SGD-momentum update with weight decay, in straightforward
``jax.numpy``.  It imports nothing of ``imagent_tpu`` and takes no
array the program has made; sizes come from the configuration file.

Departures from the published description, each on purpose:

* Initial weights are drawn the way the program's library (flax 0.12)
  draws them from the same seed (``param_key``), so that both sides
  start from the same point and three optimizer steps can be compared.
  The distributions are torchvision's He-normal (fan-out) for the
  convolutions; the classifier takes flax's default (truncated
  LeCun-normal, zero bias), where torchvision draws uniformly.
* BatchNorm's running statistics are not kept: training normalises
  with the batch's own statistics and the window never evaluates.
* Each residual block is rematerialised on the backward pass
  (``jax.checkpoint``) so that 256 rows in float32 fit one chip; the
  numbers computed are unchanged.

``quant`` (a function array -> array, or None) is the control's hook:
it is applied to both operands of every convolution and of the
classifier's matrix product, which is how the reference is "computed
in the nearest precision below" the configuration's (see checks.py).
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Structure: every parameter leaf of the network, by the path the
# program's library gives it.
# ---------------------------------------------------------------------------


def inner_width(cfg: dict, stage: int) -> int:
    planes = cfg["stem_width"] * 2 ** stage
    return int(planes * cfg["width_per_group"] / 64) * cfg["groups"]


def conv_plan(cfg: dict) -> list[dict]:
    """Every convolution in forward order: path, kernel size, stride,
    input/output channels and input/output spatial size."""
    size = cfg["image_size"]
    w0 = cfg["stem_width"]
    plan = [dict(path="conv1", k=7, stride=2, cin=3, cout=w0,
                 hin=size, hout=size // 2)]
    h = size // 4  # after the 3x3/2 max-pool
    cin = w0
    for s, n_blocks in enumerate(cfg["stage_sizes"]):
        width = inner_width(cfg, s)
        cout = w0 * 2 ** s * cfg["expansion"]
        for b in range(n_blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            name = f"layer{s + 1}_block{b}"
            plan.append(dict(path=f"{name}/Conv_0", k=1, stride=1,
                             cin=cin, cout=width, hin=h, hout=h))
            plan.append(dict(path=f"{name}/Conv_1", k=3, stride=stride,
                             cin=width, cout=width, hin=h,
                             hout=h // stride))
            plan.append(dict(path=f"{name}/Conv_2", k=1, stride=1,
                             cin=width, cout=cout, hin=h // stride,
                             hout=h // stride))
            if b == 0:
                plan.append(dict(path=f"{name}/downsample_conv", k=1,
                                 stride=stride, cin=cin, cout=cout,
                                 hin=h, hout=h // stride))
            h //= stride
            cin = cout
    return plan


def param_key(seed: int, path: str, count: int = 1):
    """The key flax 0.12 hands a parameter's initialiser: the root key
    with the SHA-1 of the scope names and the per-scope draw counter
    folded in (``flax.core.scope._fold_in_static``)."""
    m = hashlib.sha1()
    for name in path.split("/"):
        m.update(name.encode("utf-8"))
    m.update(count.to_bytes((count.bit_length() + 7) // 8, "big"))
    word = int.from_bytes(m.digest()[:4], "big")
    return jax.random.fold_in(jax.random.key(seed), jnp.uint32(word))


def init_params(cfg: dict, seed: int) -> dict:
    """Flat ``{path: float32 array}`` of every trainable leaf."""
    p = {}

    def bn(path, c):
        p[f"{path}/scale"] = jnp.ones((c,), jnp.float32)
        p[f"{path}/bias"] = jnp.zeros((c,), jnp.float32)

    for c in conv_plan(cfg):
        shape = (c["k"], c["k"], c["cin"], c["cout"])
        std = math.sqrt(2.0 / (c["k"] * c["k"] * c["cout"]))  # fan-out
        p[f"{c['path']}/kernel"] = std * jax.random.normal(
            param_key(seed, c["path"]), shape, jnp.float32)
        if c["path"] == "conv1":
            bn("bn1", c["cout"])
        elif c["path"].endswith("downsample_conv"):
            bn(c["path"].replace("downsample_conv", "downsample_bn"),
               c["cout"])
        else:
            bn(c["path"].replace("Conv_", "BatchNorm_"), c["cout"])
    feat = cfg["stem_width"] * 8 * cfg["expansion"]
    std = math.sqrt(1.0 / feat) / 0.87962566103423978  # truncated normal
    p["fc/kernel"] = std * jax.random.truncated_normal(
        param_key(seed, "fc"), -2.0, 2.0, (feat, cfg["num_classes"]),
        jnp.float32)
    p["fc/bias"] = jnp.zeros((cfg["num_classes"],), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# Forward pass and loss
# ---------------------------------------------------------------------------


def _conv(x, w, stride, quant):
    k = w.shape[0]
    if quant is not None:
        x, w = quant(x), quant(w)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((k // 2, k // 2), (k // 2, k // 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, scale, bias, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _block(p, x, stride, eps, quant):
    y = x
    for i, s in enumerate((1, stride, 1)):
        y = _conv(y, p[f"Conv_{i}/kernel"], s, quant)
        y = _bn(y, p[f"BatchNorm_{i}/scale"], p[f"BatchNorm_{i}/bias"],
                eps)
        if i < 2:
            y = jax.nn.relu(y)
    if "downsample_conv/kernel" in p:
        x = _conv(x, p["downsample_conv/kernel"], stride, quant)
        x = _bn(x, p["downsample_bn/scale"], p["downsample_bn/bias"],
                eps)
    return jax.nn.relu(x + y)


def loss_fn(params: dict, images_u8, labels, cfg: dict, quant=None):
    """Mean cross-entropy of one replica's rows (uint8 NHWC in)."""
    eps = cfg["bn_eps"]
    mean = jnp.asarray(cfg["mean"], jnp.float32)
    std = jnp.asarray(cfg["std"], jnp.float32)
    x = (images_u8.astype(jnp.float32) / 255.0 - mean) / std
    x = _conv(x, params["conv1/kernel"], 2, quant)
    x = jax.nn.relu(_bn(x, params["bn1/scale"], params["bn1/bias"], eps))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))
    for s, n_blocks in enumerate(cfg["stage_sizes"]):
        for b in range(n_blocks):
            name = f"layer{s + 1}_block{b}/"
            sub = {k[len(name):]: v for k, v in params.items()
                   if k.startswith(name)}
            stride = 2 if (s > 0 and b == 0) else 1
            x = jax.checkpoint(
                lambda p, a, st=stride: _block(p, a, st, eps, quant))(
                    sub, x)
    x = jnp.mean(x, axis=(1, 2))
    w = params["fc/kernel"]
    if quant is not None:
        x, w = quant(x), quant(w)
    logits = jnp.dot(x, w, precision=HIGHEST) + params["fc/bias"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# Three optimizer steps
# ---------------------------------------------------------------------------


def lr_at_epoch0(cfg: dict) -> float:
    """Linear warm-up by epoch: epoch 0 trains at lr / warmup_epochs."""
    w = cfg["warmup_epochs"]
    return cfg["lr"] / w if w > 0 else cfg["lr"]


def follow(cfg: dict, seed: int, batches: list, replicas: int = 1,
           quant=None, fault: str | None = None) -> dict:
    """Drive the reference through ``len(batches)`` steps from the
    seed's initial weights.  ``batches`` holds ``(images_u8, labels)``
    global batches as numpy arrays; with ``replicas`` > 1 each is cut
    into that many contiguous shards, every shard normalises with its
    own batch statistics, and the gradients are averaged (DDP).

    ``fault`` plants what a broken program would do (tests and the
    readings in PERF.md, never a benchmark run): ``half_batch`` takes
    loss and gradient over the first half of each shard's rows,
    ``no_exchange`` applies replica 0's gradient unaveraged.

    Returns ``losses`` (one per step), ``grad1`` (the first step's
    gradient, before weight decay), ``p0`` and ``p_end`` as flat dicts
    of numpy arrays.
    """
    wd, mu, lr = cfg["weight_decay"], cfg["momentum"], lr_at_epoch0(cfg)
    grad = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss_fn(p, x, y, cfg, quant)))

    @jax.jit
    def sgd(p, m, g):
        m = {k: mu * m[k] + g[k] + wd * p[k] for k in p}
        return {k: p[k] - lr * m[k] for k in p}, m

    params = init_params(cfg, seed)
    p0 = {k: np.asarray(v) for k, v in params.items()}
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad1 = [], None
    for images, labels in batches:
        rows = images.shape[0] // replicas
        take = rows // 2 if fault == "half_batch" else rows
        total, gsum = 0.0, None
        for r in range(replicas):
            sl = slice(r * rows, r * rows + take)
            loss, g = grad(params, jnp.asarray(images[sl]),
                           jnp.asarray(labels[sl]))
            total += float(loss)
            if fault == "no_exchange" and r > 0:
                continue
            gsum = g if gsum is None else {k: gsum[k] + g[k] for k in g}
        n = 1 if fault == "no_exchange" else replicas
        g = {k: v / n for k, v in gsum.items()}
        losses.append(total / replicas)
        if grad1 is None:
            grad1 = {k: np.asarray(v) for k, v in g.items()}
        params, mom = sgd(params, mom, g)
    return {"losses": losses, "grad1": grad1, "p0": p0,
            "p_end": {k: np.asarray(v) for k, v in params.items()}}
