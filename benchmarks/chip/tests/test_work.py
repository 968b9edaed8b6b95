"""The work counts of work/ against XLA's cost analysis on the CPU."""
import jax
import jax.numpy as jnp
import pytest

import common
from repro.configs.base import FLConfig
from repro.configs.registry import get_arch
from repro.core.server_pass import apply_server_round
from repro.models.lenet import apply_lenet, init_lenet, lenet_loss
from repro.models.model import build_model

whisper = common.load_module("work", "whisper_tiny")
lenet = common.load_module("work", "lenet_fmnist_paper")
WHISPER = common.load_json(common.HERE / "configs" / "whisper_tiny.json")
LENET = common.load_json(common.HERE / "configs" / "lenet_fmnist_paper.json")


def cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    return c[0] if isinstance(c, list) else c


def test_whisper_count_is_the_programs_tied_tree():
    model = get_arch("whisper-tiny").model.replace(tie_embeddings=True)
    shapes = jax.eval_shape(build_model(model).init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert whisper.n_params(WHISPER) == n == WHISPER["n_params"]


def test_lenet_count_is_the_programs_tree():
    shapes = jax.eval_shape(init_lenet, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert lenet.n_params(LENET) == n == LENET["n_params"]


@pytest.mark.parametrize("k", [4, 16])
def test_pass_bytes_against_xla(k):
    """The least bytes of a round, (2K + 2) * N * 4, are what XLA sizes
    the call's operands and results at (x, K bases, K deltas in; new x
    out), to within the (K,) scalars in and out, under 1% here. XLA's
    own traffic count of the jnp lowering can only lie above them: on
    the CPU it does not fuse eq. 3's difference into its reduction and
    moves it once more, so it reads 2-2.5 times the least."""
    n = 1 << 16
    fl = FLConfig(buffer_size=k)
    x = jnp.zeros((n,), jnp.float32)
    rows = jnp.zeros((k, n), jnp.float32)
    scal = jnp.ones((k,), jnp.float32)

    def round_(x, b, d, l, s, t):
        return apply_server_round(x, b, d, l, s, t, fl, mode="reference")

    compiled = jax.jit(round_).lower(x, rows, rows, scal, scal, scal) \
        .compile()
    mem = compiled.memory_analysis()
    io = mem.argument_size_in_bytes + mem.output_size_in_bytes
    least = whisper.pass_bytes(n, k)
    assert least <= io <= 1.01 * least, (io, least)
    traffic = cost(round_, x, rows, rows, scal, scal, scal)["bytes accessed"]
    assert least <= traffic <= 2.5 * least, (traffic, least)


def test_lenet_train_flops_against_xla():
    """Model FLOPs of one SGD step (forward + backward) on a batch of B,
    against XLA's count of the program's gradient. XLA also counts the
    elementwise work (tanh, pooling, the loss, the bias gradients) that
    model FLOPs leave out, which is 3% of it here, so the model count
    must lie within 5% below XLA's and never above it."""
    b = LENET["batch_size"]
    p = init_lenet(jax.random.PRNGKey(0))
    batch = (jnp.zeros((b, 28, 28, 1)), jnp.zeros((b,), jnp.int32))
    grad = jax.grad(lambda p, bt: lenet_loss(p, bt)[0])
    xla = cost(grad, p, batch)["flops"]
    model = b * lenet.train_flops(LENET)
    assert 0.95 * xla <= model <= xla, (model, xla)


def test_lenet_forward_flops_against_xla():
    b = LENET["batch_size"]
    p = init_lenet(jax.random.PRNGKey(0))
    xla = cost(apply_lenet, p, jnp.zeros((b, 28, 28, 1)))["flops"]
    model = b * lenet.forward_flops(LENET)
    assert 0.95 * xla <= model <= xla, (model, xla)
