"""The Pallas kernels of the main path compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot show that the TPU compiler
accepts a kernel's tiling; these tests compile against a DESCRIBED
``v5e:2x2`` topology, which needs the TPU compiler but no chip.  The
topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, so every test of this kind
lives in this one file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels import flash_attention, fused_nesterov, slowmo_update

ROWS = (4096, 1024)  # packed (rows, LANES) buffers


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _kernel_names(compiled) -> set:
    """The instruction names of the kernel calls, numbering dropped: a
    device trace names each kernel event by its instruction."""
    return {
        re.match(r"\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)* = ", line).group(1)
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    }


def _entry(hlo: str) -> dict:
    """The entry computation's instructions: name -> (opcode, shape with
    layout, operand names)."""
    body = hlo.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    out = {}
    for line in body.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = ", line)
        rest = line[m.end():]
        depth = 0
        for i, ch in enumerate(rest):  # a tuple shape holds spaces
            depth += (ch == "(") - (ch == ")")
            if ch == " " and depth == 0:
                break
        shape, rest = rest[:i], rest[i + 1:]
        opcode, args = rest.split("(", 1)
        depth, end = 1, 0
        while depth:
            depth += (args[end] == "(") - (args[end] == ")")
            end += 1
        out[m.group(1)] = (opcode, shape, re.findall(r"%([\w.-]+)", args[:end]))
    return out


def _moves_nothing(entry: dict, name: str) -> bool:
    """A bitcast, a tuple element, or a copy or prefetch (``copy-start``/
    ``copy-done``) that keeps its operand's shape and layout; the memory
    space (``S(n)``) is not layout."""
    opcode, shape, operands = entry[name]
    if opcode in ("bitcast", "get-tuple-element", "copy-done"):
        return True  # a copy-done finishes the copy-start checked below
    def layout(s):
        return re.sub(r"S\(\d+\)", "", s)
    source = layout(entry[operands[0]][1]) if operands else None
    if opcode == "copy":
        return source == layout(shape)
    return opcode == "copy-start" and layout(shape).startswith(f"({source}, ")


def _sources(entry: dict, name: str) -> set:
    """Where ``name``'s value comes from, through instructions that move
    nothing."""
    if not _moves_nothing(entry, name):
        return {f"{entry[name][0]} %{name}"}
    return set().union(*(_sources(entry, o) for o in entry[name][2]))


def _sinks(entry: dict, name: str) -> set:
    """Where ``name``'s value goes, through instructions that move nothing."""
    out = set()
    for user, (opcode, _, operands) in entry.items():
        if name in operands:
            out |= _sinks(entry, user) if _moves_nothing(entry, user) else {
                f"{opcode} %{user}"}
    return out


def test_fused_nesterov_takes_leaves_as_they_are(one_chip, no_persistent_cache, monkeypatch):
    """The tree-layout inner step on olmo-1b's leaves (width 2048, depth 2,
    one worker): every operand and result of every kernel call is the
    leaf's own buffer, in its own layout and dtype — no reshape, transpose,
    convert, layout-changing copy or fusion feeds or leaves the kernel."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import build_model

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = build_model(get_config("olmo-1b", reduced=False).replace(n_layers=2))
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def leaves(dtype):
        return jax.tree.map(
            lambda p: jax.ShapeDtypeStruct((1,) + p.shape, dtype, sharding=one_chip),
            pshape,
        )

    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    with ops.tally() as tally:
        c = _compile(
            lambda x, h, g, r: ops.fused_nesterov_update(
                x, h, g, lr=r, momentum=0.9, use_pallas=True
            ),
            leaves(jnp.bfloat16), leaves(jnp.float32), leaves(jnp.bfloat16), lr,
        )
    n = len(jax.tree.leaves(pshape))
    assert tally.summary().startswith(f"fused_nesterov: {n} of {n} leaves copy-free (100.0%")
    entry = _entry(c.as_text())
    calls = [
        k for k, v in entry.items()
        if v[0] == "custom-call" and k.startswith("fused_nesterov")
    ]
    assert len(calls) == n
    for call in calls:
        feeds = set().union(*(_sources(entry, o) for o in entry[call][2]))
        assert {f.split()[0] for f in feeds} == {"parameter"}, (call, feeds)
        assert {f.split()[0] for f in _sinks(entry, call)} == {"tuple"}, call


def test_slowmo_update_compiles(one_chip, no_persistent_cache):
    s = jax.ShapeDtypeStruct(ROWS, jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    c = _compile(
        lambda a, b, u, gamma: slowmo_update.slowmo_update_2d(
            a, b, u, gamma, alpha=1.0, beta=0.7
        ),
        s, s, s, g,
    )
    assert _kernel_calls(c) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_fused_nesterov_compiles(one_chip, no_persistent_cache, dtype):
    x = jax.ShapeDtypeStruct(ROWS, dtype, sharding=one_chip)
    s = jax.ShapeDtypeStruct(ROWS, jnp.float32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    c = _compile(
        lambda a, h, g, r: fused_nesterov.fused_nesterov_2d(a, h, g, r, momentum=0.9),
        x, s, s, lr,
    )
    assert _kernel_calls(c) == 1


def test_kernel_calls_carry_their_names(one_chip, no_persistent_cache):
    """Each kernel's ``name=`` names its custom call and ends its op path."""
    s = jax.ShapeDtypeStruct(ROWS, jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    kernels = {
        "fused_nesterov": lambda a, h, gr, r: fused_nesterov.fused_nesterov_2d(
            a, h, gr, r, momentum=0.9
        ),
        "slowmo_update": lambda a, b, u, gamma: slowmo_update.slowmo_update_2d(
            a, b, u, gamma, alpha=1.0, beta=0.7
        ),
    }
    for name, fn in kernels.items():
        c = _compile(fn, s, s, s, g)
        assert _kernel_names(c) == {name}
        calls = [
            line for line in c.as_text().splitlines() if "tpu_custom_call" in line
        ]
        assert all(f'/{name}/pallas_call"' in line for line in calls), calls


@pytest.mark.parametrize(
    "shape",
    [(1, 2048, 16, 128), (4, 512, 16, 128), (2, 100, 16, 128)],
    ids=["olmo-prefill-2048", "serve-chunk-512", "ragged-100"],
)
def test_flash_attention_compiles(one_chip, no_persistent_cache, shape):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    c = _compile(lambda q, k, v: flash_attention.flash_attention(q, k, v), q, q, q)
    assert _kernel_calls(c) == 1


@pytest.mark.parametrize(
    "B,S,H,D,Dv",
    [(2, 4096, 16, 192, 128), (2, 2048, 16, 128, 128)],
    ids=["deepseek-v2-lite-4096", "olmo-2048"],
)
def test_flash_attention_trains_at_cell_shapes(one_chip, no_persistent_cache, B, S, H, D, Dv):
    """The flash kernel's forward and its gradient compile at the two
    training cells' attention (bf16): the forward is one call, the gradient
    three (forward with its log-sum-exp, dq, dk/dv), every one a custom
    call named ``flash_attention`` whose op path ends in its name."""
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, S, H, Dv), jnp.bfloat16, sharding=one_chip)

    def attend(q, k, v):
        return flash_attention.flash_attention(q, k, v, scale=0.1)

    fwd = _compile(attend, q, q, v)
    grad = _compile(
        jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)), (0, 1, 2)),
        q, q, v,
    )
    assert _kernel_calls(fwd) == 1
    assert _kernel_calls(grad) == 3
    assert _kernel_names(fwd) == _kernel_names(grad) == {"flash_attention"}
    calls = [line for line in grad.as_text().splitlines() if "tpu_custom_call" in line]
    assert all('/flash_attention/pallas_call"' in line for line in calls), calls


def test_training_round_compiles_with_kernels(topo, no_persistent_cache, monkeypatch):
    """A packed local-SGD + SlowMo round of the REDUCED olmo-1b, through the
    shard_map path on one described chip, compiles with the kernels inside
    (the Nesterov inner step, the lines 7-8 update, and causal attention
    through the flash kernel, forward and backward)."""
    from repro.configs import get_config
    from repro.core import slowmo
    from repro.distributed import spmd
    from repro.kernels import ops
    from repro.launch.mesh import WorkerLayout
    from repro.models import build_model

    # the dispatch asks the (CPU) default backend; this compile is for a TPU
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = build_model(get_config("olmo-1b", reduced=True))
    cfg = dataclasses.replace(
        slowmo.preset("local_sgd+slowmo", num_workers=1, tau=2),
        packed=True,
        use_pallas=True,
    )
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pack = slowmo.make_state_pack_spec(cfg, pshape)
    state = jax.eval_shape(lambda p: slowmo.init_slowmo(cfg, p, pack=pack), pshape)
    batches = {"tokens": jax.ShapeDtypeStruct((2, 1, 2, 64), jnp.int32)}
    layout = WorkerLayout(
        Mesh([topo.devices[0]], ("data",)),
        worker_axes=("data",), batch_axes=(), model_axes=(),
    )
    fn = spmd.build_spmd_round(cfg, model.loss_fn, layout, state, batches, pack)
    c = fn.lower(state, batches, jax.ShapeDtypeStruct((), jnp.float32)).compile()
    assert _kernel_calls(c) >= 2
    assert _kernel_names(c) == {"fused_nesterov", "slowmo_update", "flash_attention"}


def test_expert_gmm_compiles_forward_and_backward(one_chip, no_persistent_cache):
    """The grouped products of DeepSeek-V2-Lite's expert layer at published
    widths (8 held experts, 2 x 4096 tokens, top-6, 128-row tiles): the
    forward, and the backward's transposed product and per-expert weight
    product, all custom calls named ``expert_gmm``."""
    from repro.kernels import expert_gmm as eg

    tm, held, d, f = 128, 8, 2048, 1408
    rows = (8192 * 6 // tm + held) * tm

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, wi, wo, tile_group, n_tiles):
        h = eg.expert_gmm(x, wi, tile_group, n_tiles, tm)
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        return jnp.sum(eg.expert_gmm(h, wo, tile_group, n_tiles, tm).astype(jnp.float32))

    c = _compile(
        jax.grad(layer, (0, 1, 2)),
        s((rows, d), jnp.bfloat16), s((held, d, 2 * f), jnp.bfloat16),
        s((held, f, d), jnp.bfloat16), s((rows // tm,), jnp.int32), s((), jnp.int32),
    )
    # gate/up forward; down's lhs and weight products; gate/up's lhs and
    # weight products (down's forward output is not needed by the gradient)
    assert _kernel_calls(c) == 5
    assert _kernel_names(c) == {"expert_gmm"}


def test_expert_layer_row_bounds_compile(one_chip, no_persistent_cache, monkeypatch):
    """The expert layer over bounds of 768, 1,536 and 2,304 rows (2 x 512
    tokens, top-4 of 16 experts, 2 held): the loss and gradient of the
    checkpointed layer, mapped over the one worker a chip holds as the round
    maps it, compile with one conditional for the forward and one for the
    backward, each bound's products through ``expert_gmm``."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import build_model
    from repro.models import moe

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("deepseek-v2-lite", reduced=True).replace(
        n_experts=16, top_k=4, experts_held=2, dtype=jnp.bfloat16)
    assert moe.ladder(1024, 4, 2, 16) == (768, 1536, 2304)
    p = jax.eval_shape(lambda k: jax.tree.map(lambda x: x[0], build_model(cfg).init(k)
                                              ["moe_blocks"]), jax.random.PRNGKey(0))

    def loss(p, h):
        return jnp.sum(moe.moe_ffn(cfg, p, h)[0].astype(jnp.float32))

    def s(x):
        return jax.ShapeDtypeStruct((1,) + x.shape, x.dtype, sharding=one_chip)

    c = _compile(jax.vmap(jax.value_and_grad(jax.checkpoint(loss), (0, 1))), jax.tree.map(s, p),
                 s(jax.ShapeDtypeStruct((2, 512, cfg.d_model), jnp.float32)))
    hlo = c.as_text()
    assert sum(" conditional(" in line for line in hlo.splitlines()) == 2
    assert _kernel_names(c) == {"expert_gmm"}
    # at each bound: the forward's two products; the backward's two again
    # (combine's gradient reads the down product's rows) and four
    assert _kernel_calls(c) == 3 * 8


def test_moe_round_compiles_with_the_expert_kernel(topo, no_persistent_cache, monkeypatch):
    """A packed round of the REDUCED deepseek-v2-lite (latent attention,
    dropless MoE) on one described chip runs the expert layer through
    ``expert_gmm`` and the latent attention through ``flash_attention``
    beside the fused SlowMo kernels; no loop of the attention's own (the
    chunked path's scan over kv chunks) is left under the ``mla`` scope."""
    from repro.configs import get_config
    from repro.core import slowmo
    from repro.distributed import spmd
    from repro.kernels import ops
    from repro.launch.mesh import WorkerLayout
    from repro.models import build_model

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = build_model(get_config("deepseek-v2-lite", reduced=True))
    cfg = dataclasses.replace(
        slowmo.preset("local_sgd+slowmo", num_workers=1, tau=2),
        packed=True,
        use_pallas=True,
    )
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pack = slowmo.make_state_pack_spec(cfg, pshape)
    state = jax.eval_shape(lambda p: slowmo.init_slowmo(cfg, p, pack=pack), pshape)
    batches = {"tokens": jax.ShapeDtypeStruct((2, 1, 2, 64), jnp.int32)}
    layout = WorkerLayout(
        Mesh([topo.devices[0]], ("data",)),
        worker_axes=("data",), batch_axes=(), model_axes=(),
    )
    fn = spmd.build_spmd_round(cfg, model.loss_with_stats, layout, state, batches, pack)
    c = fn.lower(state, batches, jax.ShapeDtypeStruct((), jnp.float32)).compile()
    hlo = c.as_text()
    assert _kernel_names(c) == {"fused_nesterov", "slowmo_update", "expert_gmm",
                                "flash_attention"}
    loops = [line for line in hlo.splitlines() if " while(" in line and "body=" in line]
    assert loops and not [line for line in loops if "/mla/" in line], loops
