"""Compile the main path for a TPU v5e that is described, not attached.

The Pallas kernels run interpreted everywhere but on a TPU, so only the
TPU compiler can refuse them: a layout Mosaic cannot lower, a block that
overflows scoped VMEM, a program that does not fit the device. These
tests compile, at the shapes the trainer runs (T = unroll 100, B = 32
and 128 trajectories-by-envs, chase's action count, the ``impala-deep``
agent):

* the plain and the fused V-trace kernels;
* the donated single-device train step with ``vtrace_impl="fused"``;
* the SPMD train step on a 4-device ``('data',)`` mesh.

Nothing runs: a compile that passes is not a chip run. The topology is
described inside a module fixture (never at import), so every xdist
worker collects the same tests and only the worker given this file loads
the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ImpalaConfig
from repro.configs.registry import get_config
from repro.data.envs import make_env
from repro.kernels import vtrace as vk

T = 100                 # unroll: the ImpalaConfig default (paper Table D.3)
NUM_ENVS = 32
MAX_TRAJS = 4           # the largest dynamic-batch bucket
HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described chip's compile can be written to the persistent cache
    # but never read back here: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    # the real Mosaic kernel, not the interpreter the CPU backend picks
    monkeypatch.setenv(vk.INTERPRET_ENV, "0")


@pytest.fixture(scope="module")
def chase():
    return make_env("chase")


def _agent(env):
    arch = get_config("impala-deep").replace(image_hw=env.image_hw)
    icfg = ImpalaConfig(num_actions=env.num_actions, unroll_length=T)
    return arch, icfg


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _abstract_batch(env, arch, b, sharding):
    """The trajectory batch the learner stages: b rows of T steps."""
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    lstm = (b, arch.lstm_width)
    return {
        "obs_image": sd((b, T + 1) + env.image_hw, jnp.uint8),
        "last_action": sd((b, T + 1), jnp.int32),
        "last_reward": sd((b, T + 1)),
        "done_in": sd((b, T + 1), jnp.bool_),
        "lstm_state": (sd(lstm), sd(lstm)),
        "actions": sd((b, T), jnp.int32),
        "rewards": sd((b, T)),
        "discounts": sd((b, T)),
        "behaviour_logprob": sd((b, T)),
        "done": sd((b, T), jnp.bool_),
    }


def _params_and_opt(arch, env, icfg, optimizer_fn):
    from repro.models import backbone as bb
    from repro.models import common

    specs = bb.backbone_specs(arch, env.num_actions)
    params = jax.eval_shape(
        lambda: common.init_params(specs, jax.random.key(0)))
    return params, jax.eval_shape(optimizer_fn, params)


def _record(compiled, label):
    mem = compiled.memory_analysis()
    assert mem is not None, label
    print(f"{label}: args={mem.argument_size_in_bytes} "
          f"out={mem.output_size_in_bytes} temp={mem.temp_size_in_bytes} "
          f"code={mem.generated_code_size_in_bytes}")
    return mem


@pytest.mark.parametrize("kernel", ["plain", "fused"])
@pytest.mark.parametrize("b", [NUM_ENVS, NUM_ENVS * MAX_TRAJS])
def test_vtrace_kernel_compiles_for_v5e(topo, one_chip, compiled_kernels,
                                        chase, kernel, b):
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    if kernel == "plain":
        fn = vk.vtrace_pallas
        args = [s(T, b)] * 6
    else:
        fn = vk.loss_vtrace_pallas
        a = chase.num_actions
        args = [s(T, b, a), s(T, b, a)] + [s(T, b)] * 5
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _record(compiled, f"{kernel} T={T} B={b}")


@pytest.mark.timeout_s(900)
def test_impala_deep_donated_train_step_compiles_for_v5e(
        topo, one_chip, compiled_kernels, chase):
    from repro.core import learner as learner_lib

    arch, icfg = _agent(chase)
    step, opt = learner_lib.build_train_step(
        arch, icfg, chase.num_actions, vtrace_impl="fused")
    params, opt_state = _params_and_opt(arch, chase, icfg, opt.init)
    b = NUM_ENVS * MAX_TRAJS
    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
        _abstract(params, one_chip), _abstract(opt_state, one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        _abstract_batch(chase, arch, b, one_chip))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # the fused loss/V-trace kernel is in the step, compiled by Mosaic
    assert "tpu_custom_call" in hlo
    mem = _record(compiled, f"impala-deep train step B={b}")
    assert mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.timeout_s(900)
def test_spmd_train_step_compiles_on_v5e_4_device_mesh(
        topo, compiled_kernels, chase):
    from repro.core import learner as learner_lib
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((4,), ("data",), devices=topo.devices)
    assert len(set(mesh.devices.flat)) == 4
    arch, icfg = _agent(chase)
    step, opt = learner_lib.build_spmd_train_step(
        arch, icfg, chase.num_actions, mesh, vtrace_impl="fused")
    params, opt_state = _params_and_opt(arch, chase, icfg, opt.init)
    repl = NamedSharding(mesh, P())
    b = NUM_ENVS * MAX_TRAJS
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        _abstract(params, repl), _abstract(opt_state, repl),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        _abstract_batch(chase, arch, b,
                        NamedSharding(mesh, P("data")))).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # the gradient mean is an in-XLA collective over the mesh
    assert "all-reduce" in hlo
    mem = _record(compiled, f"spmd train step 4 devices B={b}")
    assert mem.temp_size_in_bytes < HBM_BYTES
