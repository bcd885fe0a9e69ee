"""Compile the main path's programs for a DESCRIBED TPU v5e — no chip.

The TPU compiler is installed beside JAX and compiles for a ``v5e:2x2``
topology that is described, not attached, so what the chip's compiler would
refuse (a Mosaic tiling rule, a VMEM limit, a kernel that cannot be
partitioned, a program that does not fit HBM) is refused here, on the CPU
host, at no chip time. Nothing runs: a passing compile is not a chip run.

Rules this file follows (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture that skips when it cannot be —
never at import, in a ``skipif``, in ``parametrize`` arguments or in
conftest.py; everything compiles in the test's own process (the process
that loaded libtpu keeps its lock until exit); the persistent compile cache
is off around the compiles (a described-chip entry cannot be read back
without a chip); all such tests live in this ONE file so one xdist worker
owns the library.

The program picks its kernels by ``jax.default_backend()``, which is the CPU
here, so the tests steer it themselves (``tpu_backend``) — no option of the
program exists for that.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip_compile(topo):
    """Module-wide compile environment: persistent cache off (its entries
    for a described chip cannot be read back), and the program's own
    DEFAULT matmul precision instead of the suite's "highest" pin (which
    has no bf16 meaning inside Mosaic — ops/attention.py ``_dot``)."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.config.update("jax_default_matmul_precision", None)
    yield topo
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(chip_compile):
    return SingleDeviceSharding(chip_compile.devices[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """The package asks ``jax.default_backend()`` to choose kernel vs XLA
    fallback and compiled vs interpreted; answer for the described chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(sharding, tree):
    """Shapes of ``tree`` placed by ``sharding`` (one sharding, or a
    matching tree of them) — a described device cannot hold arrays."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, sharding)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# -- kernels ----------------------------------------------------------------

ATTENTION_SHAPES = [
    # (batch, heads, seq, head_dim), dtype, local_window
    pytest.param((2, 4, 202, 64), jnp.float32, None, id="full_kv_f32_w202"),
    pytest.param((2, 2, 256, 128), jnp.bfloat16, None, id="full_kv_bf16"),
    pytest.param((8, 2, 1225, 128), jnp.bfloat16, 202, id="banded_1225"),
    pytest.param((2, 2, 6046, 128), jnp.bfloat16, 202,
                 id="streaming_full_episode"),
    pytest.param((2, 8, 8192, 128), jnp.bfloat16, 202, id="streaming_8k"),
    pytest.param((1, 2, 32969, 128), jnp.float32, 202,
                 id="streaming_32k_f32"),
]


@pytest.mark.parametrize("shape,dtype,window", ATTENTION_SHAPES)
def test_attention_fwd_bwd_compiles_for_v5e(one_chip, tpu_backend, shape,
                                            dtype, window):
    from sharetrade_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, local_window=window,
                              use_pallas=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    # Forward + dQ + dK/dV (the value_and_grad forward is the residual-
    # saving one; XLA drops the unused plain forward).
    assert _mosaic_calls(compiled) >= 3


def _relayouts(text: str, elements: int) -> list[str]:
    """``copy`` / ``reshape`` / ``transpose`` instructions of a compiled text
    whose array result holds ``elements`` elements, in any shape: a leaf
    laid out again, or viewed as another shape. (An async ``copy-start``
    that stages a leaf in another memory space keeps its layout and is not
    one of these.)"""
    import re
    return [f"{name} = [{dims}] {op}" for name, dims, op in re.findall(
        r"(%[\w.\-]+) = \w+\[([\d,]*)\]\S* (copy|reshape|transpose)\(",
        text) if _elements(dims) == elements]


@pytest.mark.parametrize("optimizer", ["adagrad", "adam", "sgd"])
def test_fused_update_compiles_for_v5e(one_chip, tpu_backend, optimizer):
    """The update is XLA's own elementwise fusion over the leaf as stored,
    with the backend answering "tpu" as on the chip: no Mosaic call, and
    no copy or reshape of the leaf into another view and back (the Pallas
    kernel's ``(rows, 128)`` view cost the v5e 2.26 ms of relayouts an
    update of the d=1024 tree: PERF.md §5)."""
    from sharetrade_tpu.agents.base import build_optimizer
    from sharetrade_tpu.config import LearnerConfig
    from sharetrade_tpu.ops.fused_update import fused_apply

    params = {"w": jnp.zeros((1024, 4096), jnp.float32)}
    state = jax.eval_shape(
        build_optimizer(LearnerConfig(optimizer=optimizer)).init, params)
    grads = {"w": jax.ShapeDtypeStruct((1024, 4096), jnp.bfloat16)}

    def update(g, s, p):
        return fused_apply(optimizer, 0.01, g, s, p,
                           compute_dtype=jnp.bfloat16, emit_compute=True)

    compiled = jax.jit(update).lower(
        _on(one_chip, grads), _on(one_chip, state),
        _on(one_chip, params)).compile()
    text = compiled.as_text()
    assert _mosaic_calls(compiled) == 0
    assert not _relayouts(text, 1024 * 4096)
    assert " fusion(" in text


def test_fused_update_compiles_under_tp_specs_for_v5e(chip_compile,
                                                     tpu_backend):
    """On a dp x tp mesh the compiler partitions the update by each leaf's
    own Megatron spec: every device updates the shard it holds, with no
    collective, and every result keeps its leaf's spec."""
    from jax.sharding import NamedSharding
    from sharetrade_tpu.agents.base import build_optimizer
    from sharetrade_tpu.config import LearnerConfig
    from sharetrade_tpu.ops.fused_update import fused_apply
    from sharetrade_tpu.parallel.sharding import (mlp_tp_rules,
                                                  param_shardings)
    mesh = Mesh(np.array(chip_compile.devices).reshape(2, 2), ("dp", "tp"))
    rules = mlp_tp_rules()
    params = {"qkv": {"w": jnp.zeros((1024, 3072), jnp.float32)},
              "proj": {"w": jnp.zeros((1024, 1024), jnp.float32)}}
    shardings = param_shardings(params, mesh, rules)
    assert {s.spec for s in jax.tree.leaves(shardings)} == {
        P(None, "tp"), P("tp", None)}
    state = jax.eval_shape(
        build_optimizer(LearnerConfig(optimizer="adam")).init, params)
    # The state placed as the TrainState places it: moments like their
    # parameter, the step count replicated.
    state_shardings = (state[0]._replace(
        count=NamedSharding(mesh, P()), mu=shardings, nu=shardings),
        state[1])
    grads = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)

    def update(g, s, p):
        return fused_apply("adam", 0.01, g, s, p, compute_dtype=jnp.bfloat16)

    compiled = jax.jit(update).lower(
        _on(shardings, grads), _on(state_shardings, state),
        _on(shardings, params)).compile()
    text = compiled.as_text()
    assert _mosaic_calls(compiled) == 0
    assert "all-gather" not in text and "all-reduce" not in text
    new_params = compiled.output_shardings[0]
    assert jax.tree.map(lambda s: s.spec, new_params) == jax.tree.map(
        lambda s: s.spec, shardings)


# -- whole programs at the widest supported model ---------------------------
# Config and agent are chip_smoke.py's own (``ppo_tr_episode_large_d1024``,
# horizon of 3 chunks), so what compiles here is what its train_wide phase
# runs on the chip.

def _wide_config():
    import chip_smoke
    return chip_smoke.wide_config(seed=0)


def _wide_agent(cfg, mesh=None):
    import chip_smoke
    return chip_smoke.wide_agent(cfg, mesh=mesh)


@pytest.fixture(scope="module")
def wide_step(one_chip):
    """The d=1024 PPO step compiled once for one described chip: the
    agent, its state's shapes and the compiled program."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        agent = _wide_agent(_wide_config())
        ts = jax.eval_shape(agent.init, jax.random.PRNGKey(0))
        compiled = jax.jit(agent.step, donate_argnums=(0,)).lower(
            _on(one_chip, ts)).compile()
    return agent, ts, compiled


#: Temporaries of the d=1024 step with the optimiser update as a Pallas
#: kernel, at the benchmark's 1,024 agents (described-chip compile, PERF.md
#: §4): the kernel's padded views were part of them.
KERNEL_PATH_STEP_TEMP_BYTES = 390_668_288


def test_agent_step_updates_without_a_kernel_or_relayout(wide_step):
    """The optimiser update inside the d=1024 step is XLA's own: no
    ``fused_update`` Mosaic call, no copy, reshape or transpose under the
    ``update`` scope, and the step's temporaries no larger than the
    kernel path's."""
    import re
    _, _, compiled = wide_step
    text = compiled.as_text()
    kernel = r'"kernel"\s*:\s*"{}"'
    assert re.search(kernel.format("flash_fwd"), text)    # the ids still read
    assert not re.search(kernel.format("fused_update"), text)
    moved = re.findall(
        r"(%[\w.\-]+) = \S+ (copy|reshape|transpose)\(.*op_name=\"[^\"]*/update/",
        text)
    assert not moved, moved
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= KERNEL_PATH_STEP_TEMP_BYTES)


def test_agent_step_compiles_for_one_v5e(wide_step):
    _, _, compiled = wide_step
    assert _mosaic_calls(compiled) > 0
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 1024 ** 3)       # one v5e chip's HBM


def _elements(dims: str) -> int:
    """Elements of a shape as the compiled text prints it ("64,4,8")."""
    import math
    return math.prod(int(d) for d in dims.split(",") if d)


def _shape_of(text: str) -> dict:
    """Instruction name -> its array result's dims, over a compiled text."""
    import re
    return dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text))


def test_agent_step_moves_no_kv_cache_per_minibatch(wide_step):
    """The shared-trunk replay reads ``hist``, ``t`` and row health of the
    unroll-start carry, so the update phase holds no K/V cache: nothing in
    the compiled step is minibatch-by-K/V shaped (the parent gathered
    ``[mb, L, H, W, Dh]`` 16 times a chunk and scanned it for NaNs each
    time: over half of the d=1024 chunk on the chip, PERF.md PR 25), and
    the caches are checked for finiteness at most once, by the rollout's
    election, whose pass the replay's health vector shares.

    The health pass is found by the ``rows_finite`` scope in the
    instructions' ``op_name``: the fusions under it that read a whole
    ``[B, L, H, W, Dh]`` carry leaf. Each leaves at most one window slot's
    worth of it, and none reduces a leaf over ``dimensions={1,2,3,4}``
    straight to a ``[B]`` result: that form cost the chip 6 us an agent row
    however short the row (PERF.md PR 35). This reads the program's
    structure, not its speed: only a chip run says what the two-stage form
    takes."""
    import re
    agent, ts, compiled = wide_step
    text = compiled.as_text()
    batch, layers, heads, width, head_dim = ts.carry["k"].shape
    mb = batch // 4                     # learner.ppo_minibatches
    assert agent.replay_carry_bytes < 1 << 20
    # XLA splits the window axis (201 = 128 + 73): match any width.
    per_mb = re.findall(
        rf"\[{mb},{layers},{heads},\d+,{head_dim}\]", text)
    assert not per_mb, f"{len(per_mb)} minibatch-by-K/V shaped values"
    leaf = f"{batch},{layers},{heads},{width},{head_dim}"
    shape_of = _shape_of(text)
    passes = [
        (name, dims, operands)
        for name, dims, operands in re.findall(
            r"(%[\w.\-]+) = \w+\[([\d,]*)\]\S* fusion\(([^)]*)\)"
            r".*op_name=\"[^\"]*/rows_finite/", text)
        if leaf in [shape_of[o] for o in re.findall(r"%[\w.\-]+", operands)]]
    checked = sum(_elements(shape_of[o]) for _, _, operands in passes
                  for o in re.findall(r"%[\w.\-]+", operands))
    assert 0 < checked <= 2 * _elements(leaf), passes
    assert all(_elements(dims) <= _elements(leaf) // width
               for _, dims, _ in passes), passes
    per_row = [
        f"{name} = [{dims}] reduce({operand})"
        for name, dims, operand in re.findall(
            r"(%[\w.\-]+) = \w+\[([\d,]*)\]\S* reduce\((%[\w.\-]+),"
            r"[^)]*\), dimensions=\{1,2,3,4\}", text)
        if shape_of[operand] == leaf and dims == str(batch)]
    assert not per_row, per_row


def test_agent_step_gathers_no_action_log_prob(wide_step):
    """The replay reads the taken action's log-prob by a select over the
    action axis (``agents/rollout.py taken_action_log_prob``): the compiled
    step holds no gather or scatter over the replay's ``[unroll, mb, A]``
    log-probs, and no element-by-element gather (every slice size 1) of
    ``unroll * mb`` results — ``take_along_axis`` was one, a serial fusion
    of 12.5 ns an element on the chip, 15% of the d=1024 chunk and half of
    the d=256 one (PERF.md PR 31). The minibatch's own column gathers are
    ``[unroll, mb]`` too, a whole column a slice: they stay."""
    import re
    agent, _, compiled = wide_step
    text = compiled.as_text()
    per_step = agent.steps_per_chunk * (agent.num_agents // 4)   # unroll * mb
    per_action = per_step * agent.model.num_actions
    shape_of = _shape_of(text)
    moves = re.findall(
        r"(%[\w.\-]+) = \w+\[([\d,]*)\]\S* (gather|scatter)"
        r"\(([^)]*)\)(.*)", text)
    assert moves            # the minibatch gathers: the pattern still reads
    found = []
    for name, dims, op, operands, rest in moves:
        sizes = [_elements(dims)] + [
            _elements(shape_of[o]) for o in re.findall(r"%[\w.\-]+", operands)]
        by_element = re.search(r"slice_sizes=\{1(,1)*\}", rest) is not None
        if per_action in sizes or (by_element and sizes[0] == per_step):
            found.append(f"{name} = [{dims}] {op}({operands})")
    assert not found, found


def _window_config():
    """A window-mode transformer PPO (the other model file that calls the
    flash kernel), small: its dp=4 step must keep the batch dp-sharded
    through the kernel's shard_map."""
    cfg = _wide_config()
    cfg.model.seq_mode = "window"
    cfg.model.num_layers, cfg.model.num_heads, cfg.model.head_dim = 2, 4, 64
    cfg.parallel.num_workers = 16
    cfg.runtime.chunk_steps = cfg.learner.unroll_len = 32
    return cfg


@pytest.mark.parametrize("mesh_shape,make_cfg", [
    pytest.param({"dp": 4}, _wide_config, id="wide_dp4"),
    pytest.param({"dp": 4}, _window_config, id="window_dp4"),
])
def test_agent_step_compiles_for_v5e_mesh(chip_compile, tpu_backend,
                                          mesh_shape, make_cfg):
    """``cli train --mesh``: a bare ``pallas_call`` inside the partitioned
    program is refused ("Mosaic kernels cannot be automatically
    partitioned" — wide_dp4 was once refused so), so the attention
    kernel runs under a shard_map over the batch axis on a multi-device
    mesh and stays a kernel; the optimiser update is plain XLA, which the
    compiler partitions. (wide on dp2 x tp2 also compiles, 18 s: ROADMAP
    S7.)"""
    from sharetrade_tpu.parallel.sharding import (jit_parallel_step,
                                                  mesh_param_rules)
    mesh = Mesh(np.array(chip_compile.devices).reshape(
        tuple(mesh_shape.values())), tuple(mesh_shape))
    agent = _wide_agent(make_cfg(), mesh=mesh)
    ts = jax.eval_shape(agent.init, jax.random.PRNGKey(0))
    shardings, step = jit_parallel_step(
        agent, mesh, ts, param_rules=mesh_param_rules(mesh))
    compiled = step.lower(_on(shardings, ts)).compile()
    assert _mosaic_calls(compiled) > 0
    assert " all-reduce" in compiled.as_text()      # the dp gradient mean


@pytest.fixture(scope="module")
def wide_engine():
    """A ``ServeEngine`` over the widest model with train_wide's model keys
    (bf16_mixed carries), built on the host; its four device programs are
    what ``test_serve_program_compiles_for_v5e`` lowers."""
    from sharetrade_tpu.precision import policy_from_config
    from sharetrade_tpu.serve import ServeEngine
    cfg = _wide_config()
    cfg.serve.max_batch, cfg.serve.slots = 8, 16
    cfg.serve.warm_bytes = 1 << 30
    agent = _wide_agent(cfg)
    params = agent.init(jax.random.PRNGKey(0)).params
    engine = ServeEngine(agent.model, cfg.serve, params,
                         precision=policy_from_config(cfg.precision))
    yield engine, cfg
    engine.stop(drain=False)


def _compile_serve_program(one_chip, engine, cfg, program, slots=None):
    """One of the engine's device programs compiled for the described
    chip, over an arena of ``slots`` + ``max_batch`` rows (the engine's
    own by default; the programs take any number of rows)."""
    batch = cfg.serve.max_batch
    params = _on(one_chip, engine._live.params)
    n_arena = (cfg.serve.slots if slots is None else slots) + batch
    pool = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (n_arena,) + x.shape[1:], x.dtype, sharding=one_chip), engine._pool)
    obs = jax.ShapeDtypeStruct((batch, cfg.env.window + 2), jnp.float32,
                               sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    rows = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (batch,) + x.shape[1:], x.dtype, sharding=one_chip), pool)
    fn, args = {
        "warm": (jax.jit(engine._warm_program, donate_argnums=(1,)),
                 (params, pool, obs, idx)),
        "cold": (jax.jit(engine._cold_program, donate_argnums=(1,)),
                 (params, pool, obs, idx)),
        "park": (jax.jit(engine._park_program), (pool, idx)),
        "install": (jax.jit(engine._install_program, donate_argnums=(0,)),
                    (pool, rows, idx)),
    }[program]
    return fn.lower(*args).compile(), pool


@pytest.mark.parametrize("program", ["warm", "cold", "park", "install"])
def test_serve_program_compiles_for_v5e(one_chip, tpu_backend, wide_engine,
                                        program):
    engine, cfg = wide_engine
    compiled, _ = _compile_serve_program(one_chip, engine, cfg, program)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 1024 ** 3)
    if program == "cold":
        # The batched prefill attends L*(window-1)+1 rows per session
        # through the local flash kernel.
        assert _mosaic_calls(compiled) > 0


@pytest.mark.parametrize("program", ["warm", "park"])
def test_serve_gather_holds_no_arena_sized_temporary(one_chip, tpu_backend,
                                                     wide_engine, program):
    """The programs that gather from the arena read the ``max_batch`` rows
    they are given and nothing else: at 256 slots for 8 rows a tick the
    compiled program's temporaries stay under a quarter of the arena, and
    no value holds every row of a K/V leaf over a part of the window (the
    parent's ``x[idx]`` sliced each whole leaf into a 128-wide and a
    73-wide copy first: a third of the arena as temporaries at this shape,
    290 and 277 MB of 870 MB, and 73% of a warm tick on the chip; PERF.md
    PR 29)."""
    import re
    engine, cfg = wide_engine
    compiled, pool = _compile_serve_program(one_chip, engine, cfg, program,
                                            slots=256)
    arena_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(pool))
    gather_bytes = engine.registry.latest("serve_tick_gather_bytes")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < arena_bytes // 4
    # At most the gathered rows and the model's new ones.
    assert temp <= 2 * gather_bytes
    n_arena, layers, heads, window, head_dim = pool["k"].shape
    widths = {int(w) for w in re.findall(
        rf"\[{n_arena},{layers},{heads},(\d+),{head_dim}\]",
        compiled.as_text())}
    assert widths <= {window}, f"whole-arena slices of widths {widths}"


def test_published_latent_moe_warm_tick_moves_no_bank_and_no_arena(
        one_chip, tpu_backend):
    """The warm tick of ``xing4_29b_ep2`` (chipbench/configs) at its
    published widths, 1,024 slots and 64 rows: it fits the chip, and its
    temporaries are the gathered rows and the rows' activations alone. The
    first layouts tried did not pass this: an arena row of 201 ticks had the
    compiler relay out the whole arena around the gather and the scatter
    (2.3 GB of temporaries for 1.2 GB of bfloat16 rows), and an expert bank stored by expert, or
    a fused [W_gate | W_up], was copied whole every tick (4 x 470 MB)
    (described-chip compiles, PR 33)."""
    import re
    import types

    from chipbench.harness import common
    from sharetrade_tpu.models import build_model
    from sharetrade_tpu.serve.engine import ServeEngine
    manifest = common.Manifest()
    cell = manifest.cell("serve_xing4_steady")
    cfg = common.build_config(manifest.config(cell["config"]),
                              manifest.traffic(cell["traffic"]), seed=1)
    model = build_model(cfg.model, cfg.env.window + 2, head="ac")

    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch, slots = cfg.serve.max_batch, cfg.serve.slots
    pool = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (slots + batch,) + x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(model.init_carry))     # float32 rings: cast_carry
    obs = jax.ShapeDtypeStruct((batch, cfg.env.window + 2), jnp.float32,
                               sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    engine = types.SimpleNamespace(model=model)
    compiled = jax.jit(
        types.MethodType(ServeEngine._warm_program, engine),
        donate_argnums=(1,)).lower(params, pool, obs, idx).compile()
    mem = compiled.memory_analysis()
    weights = sum(int(np.prod(x.shape)) * 2 for x in jax.tree.leaves(params))
    arena = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(pool))
    assert 3.39e9 < weights < 3.41e9 and 2.8e9 < arena < 3.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert mem.alias_size_in_bytes >= arena          # updated in place
    assert mem.temp_size_in_bytes < arena // 4       # 0.5 GB, not 2.4
    text = compiled.as_text()
    held, ffn, width = (cfg.model.moe_held_experts, cfg.model.moe_ffn_dim,
                        cfg.model.hidden_dim)
    bank = rf"bf16\[({width},{held * ffn}|{held * ffn},{width})\]"
    assert not re.search(rf"= {bank}\S* copy\(", text)
    assert not re.search(rf"= f32\[{slots + batch},\d+,\d+,\d+\]\S* copy\(",
                         text)
