"""The first steps of a training run, followed by the plain reference.

``follow`` drives ``train_loss`` of a reference module through the same
batches as the program, from the same starting variables, with a plain
optimizer of its own: no optax, nothing of the program. It returns what
the comparison needs (``benchmark/train_check.py``): each step's loss, the
first step's gradient, and how far every parameter moved over the steps.

The optimizer is the traffic file's ``optimizer``: ``{"name": "sgd",
"learning_rate", "momentum"}`` (heavy ball: ``t = g + momentum * t``,
``p -= lr * t``) or ``{"name": "adam", "learning_rate", "b1", "b2",
"eps"}`` (Kingma & Ba with bias correction, eps outside the root), both
at a constant rate and without weight decay, as the cells' flags leave
them.

``fp8_rounding`` is the control's precision: the reference computed with
both inputs of every matrix product and convolution rounded to an 8-bit
float (e4m3), the step below the bfloat16 the configurations state.

Memory: the step holds 16 bytes a parameter under adam (the parameters,
the two moments, the gradient; 12 under sgd) and its activations. It
donates the parameters and the optimizer's state, so that an update
writes in place; the parameters before the first step stay on the host,
and the caller's arrays are never donated. A step that cannot fit the
device is refused before it runs (:class:`StepDoesNotFit`), with its
compiled bytes and the device's limit.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.resnet import Mode

E4M3_MAX = 240.0     # 4 exponent bits, 3 of mantissa, the top exponent kept


def _straight_through(x, rounded):
    return x + jax.lax.stop_gradient(rounded - x)


def fp8_rounding(x):
    """``x`` rounded to a float of 4 exponent and 3 mantissa bits, scaled
    per tensor so that its largest magnitude lands on the format's largest
    (the usual fp8 recipe), with the identity as its derivative.
    ``reduce_precision`` and not a pair of ``astype``: the TPU compiler
    drops a conversion to a narrower type and back as excess precision it
    may keep (the pair read a gap of exactly 0 on the chip, PR 28)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return _straight_through(
        x, jax.lax.reduce_precision(x / scale, 4, 3) * scale)


def bf16_rounding(x):
    """``x`` rounded to bfloat16's 8 exponent and 7 mantissa bits, with
    the identity as its derivative: the precision the configurations
    state, for telling what rounding alone costs from what a program
    does."""
    return _straight_through(x, jax.lax.reduce_precision(x, 8, 7))


def _zeros(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def optimizer_init(spec: dict, params):
    if spec["name"] == "sgd":
        return {"trace": _zeros(params)}
    if spec["name"] == "adam":
        return {"mu": _zeros(params), "nu": _zeros(params),
                "count": jnp.zeros((), jnp.float32)}
    raise ValueError(f"the reference has no optimizer {spec['name']!r}")


def optimizer_update(spec: dict, params, grads, opt):
    """One update: ``(new params, new optimizer state)``."""
    lr = float(spec["learning_rate"])
    tmap = jax.tree_util.tree_map
    if spec["name"] == "sgd":
        trace = tmap(lambda g, t: g + float(spec["momentum"]) * t, grads,
                     opt["trace"])
        return tmap(lambda p, t: p - lr * t, params, trace), {"trace": trace}
    b1, b2, eps = (float(spec[k]) for k in ("b1", "b2", "eps"))
    count = opt["count"] + 1.0
    mu = tmap(lambda g, m: b1 * m + (1.0 - b1) * g, grads, opt["mu"])
    nu = tmap(lambda g, v: b2 * v + (1.0 - b2) * g * g, grads, opt["nu"])
    new = tmap(lambda p, m, v: p - lr * (m / (1.0 - b1 ** count))
               / (jnp.sqrt(v / (1.0 - b2 ** count)) + eps), params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


def first_gradient_from_moment(spec: dict, moment):
    """The gradient of step 1 from the optimizer's first moment after it:
    the trace itself under sgd, ``mu / (1 - b1)`` under adam."""
    if spec["name"] == "sgd":
        return moment
    k = 1.0 / (1.0 - float(spec["b1"]))
    return jax.tree_util.tree_map(lambda m: m * k, moment)


class StepDoesNotFit(RuntimeError):
    """The reference's compiled step needs more of a device's memory than
    the device has: it is refused before it runs."""


def bytes_limit():
    """The bytes that the first device may hold, as its ``memory_stats``
    report them; None where it reports none (a CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


@functools.lru_cache(maxsize=None)
def _step(ref, config_json: str, optimizer_json: str, rounding, stat_groups):
    """One jitted training step of the reference, built once for each way
    of computing it (a process that reads many seeds compiles each once).
    The parameters and the optimizer's state are donated: the update writes
    in place."""
    config, optimizer = json.loads(config_json), json.loads(optimizer_json)
    mode = Mode(train=True, rounding=rounding, stat_groups=stat_groups,
                remat=True)

    def loss_of(params, rest, images, labels):
        return ref.train_loss({"params": params, **rest}, images, labels,
                              config, mode)

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(params, rest, opt, images, labels):
        loss, grads = jax.value_and_grad(loss_of)(params, rest, images,
                                                  labels)
        new, opt = optimizer_update(optimizer, params, grads, opt)
        return new, opt, loss, grads
    return step


def _step_memory(compiled, parameters: int) -> dict:
    """What the compiled step holds on a device while it runs, in bytes:
    its arguments and outputs, less the outputs written in place of a
    donated argument, and its temporaries."""
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return {"argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes),
            "alias": int(m.alias_size_in_bytes),
            "temp": int(m.temp_size_in_bytes), "step": int(need),
            "parameters": int(parameters)}


_COMPILED = {}


def _compiled(step, *args):
    """``step`` compiled for the shapes, types and placements of ``args``,
    once a process for each (the jit's own cache does not hold an ahead of
    time compile): the object whose memory is read is the one that runs."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    key = (step, tree, tuple((np.shape(a), np.dtype(a.dtype),
                              getattr(a, "sharding", None)) for a in leaves))
    if key not in _COMPILED:
        with jax.default_matmul_precision("highest"):
            _COMPILED[key] = step.lower(*args).compile()
    return _COMPILED[key]


def _check_fits(memory: dict) -> None:
    limit = bytes_limit()
    if limit is not None and memory["step"] > limit:
        raise StepDoesNotFit(
            f"the reference's step needs {memory['step']} bytes "
            f"({memory['step'] / memory['parameters']:.1f} B a parameter "
            f"over {memory['parameters']} parameters: arguments "
            f"{memory['argument']} + outputs {memory['output']} - aliased "
            f"{memory['alias']} + temporaries {memory['temp']}) and the "
            f"device's bytes_limit is {limit}: it is not run")


def follow(ref, variables, batches, config, optimizer: dict, *,
           rounding=None, stat_groups: int = 1, rows=None, put=None):
    """Train ``variables["params"]`` through ``batches`` (dicts with
    ``image`` and ``label``) as the reference would.

    Returns ``{"losses": [float], "gradient": tree, "change": tree,
    "memory": dict}``: the loss of every step, the gradient of the first,
    and the parameters after the last step less those before the first, as
    host arrays; and the compiled step's bytes on a device
    (``_step_memory``). ``rows`` keeps only those rows of every batch, the
    mean taken over them (the fault of a part of the batch left out);
    ``put`` places a batch's arrays (over a mesh, say) before the step
    takes them.

    The step runs on a device copy of the parameters of its own, which it
    donates: the caller's arrays are neither donated nor changed, and the
    parameters before the first step are kept on the host. So a device
    holds the parameters, the optimizer's state and one step's gradient (16
    bytes a parameter under adam) and the step's activations. Raises
    :class:`StepDoesNotFit` before the first step where that is more than
    the device holds."""
    step = _step(ref, json.dumps(config, sort_keys=True),
                 json.dumps(optimizer, sort_keys=True), rounding,
                 int(stat_groups))
    rest = {k: v for k, v in variables.items() if k != "params"}
    start = jax.device_get(variables["params"])
    feed = []
    for batch in batches:
        images, labels = batch["image"], batch["label"]
        if rows is not None:
            images, labels = images[rows], labels[rows]
        if put is not None:
            images, labels = put(images), put(labels)
        feed.append((images, labels))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), start)
    compiled = _compiled(
        step, shapes, rest,
        jax.eval_shape(lambda p: optimizer_init(optimizer, p), shapes),
        *feed[0])
    memory = _step_memory(compiled, sum(
        np.size(a) for a in jax.tree_util.tree_leaves(start)))
    _check_fits(memory)
    params = jax.tree_util.tree_map(jnp.array, start)
    opt = optimizer_init(optimizer, params)
    losses, gradient = [], None
    for images, labels in feed:
        params, opt, loss, grads = compiled(params, rest, opt, images,
                                            labels)
        losses.append(float(loss))
        if gradient is None:
            gradient = jax.device_get(grads)
        del grads
    change = jax.tree_util.tree_map(lambda a, b: a - b,
                                    jax.device_get(params), start)
    return {"losses": losses, "gradient": gradient, "change": change,
            "memory": memory}
