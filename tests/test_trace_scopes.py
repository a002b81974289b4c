"""The program's names in the device trace: phase and kernel scopes in the
compiled HLO of both step builders, the scope table read from the live
executables, ``scope_of``'s precedence, the host spans on the profiler's
clock, and the benchmark's reduction of all that against its hand-made
trace."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import profiler

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FWD = "jit(step)/jvp(hvd.forward)"
_BWD = "jit(step)/transpose(jvp(hvd.forward))"


def _bn_mlp():
    import flax.linen as nn

    class BnMLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Dense(16)(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            return nn.Dense(4)(nn.relu(x))

    return BnMLP()


def _built(hvd, builder, family):
    """``(step, args)`` of one builder on one model family, uninstrumented."""
    from horovod_tpu import training
    from horovod_tpu.models import TransformerTiny

    n = hvd.size()
    if family == "transformer":
        model = TransformerTiny(vocab=64, depth=1, heads=2, max_len=16)
        xs = np.random.RandomState(0).randint(0, 64, (n, 16), np.int32)
        ys, loss_fn = np.roll(xs, -1, 1), training.token_xent
    else:
        model = _bn_mlp()
        xs = np.random.RandomState(0).rand(2 * n, 6).astype(np.float32)
        ys = np.random.RandomState(1).randint(0, 4, 2 * n)
        loss_fn = training.softmax_xent
    params, stats = training.init_model(model, jax.random.PRNGKey(0), xs[:1])
    if builder == "jit":
        tx = hvd.DistributedOptimizer(optax.adam(1e-2))
        make = training.make_jit_train_step
    else:
        tx = optax.adam(1e-2)
        make = training.make_shardmap_train_step
    step = make(model, tx, loss_fn=loss_fn, instrument=False, donate=False)
    args = (training.replicate(params), training.replicate(stats),
            training.replicate(tx.init(params)),
            training.shard_batch(xs), training.shard_batch(ys))
    return step, args


def _phases(table):
    return {profiler.scope_of(*entry)[0] for entry in table.values()}


@pytest.mark.parametrize("family", ["transformer", "batchnorm"])
@pytest.mark.parametrize("builder", ["jit", "shardmap"])
def test_compiled_step_carries_phase_scopes(hvd, builder, family):
    step, args = _built(hvd, builder, family)
    lowered = step.lower(*args)
    compiled = lowered.compile()
    table = profiler._parse_hlo(compiled.as_text())
    assert {"forward", "backward", "optimizer", "sync"} <= _phases(table)
    names = [op for op, _ in table.values()]
    assert any("jvp(hvd.forward)" in op and "transpose(" not in op
               for op in names)
    assert any("transpose(jvp(hvd.forward))" in op for op in names)
    assert any("hvd.optimizer" in op for op in names)

    reduces = [(op, kind) for op, kind in table.values()
               if kind.startswith("all-reduce")]
    assert reduces
    # by kind alone: the partitioner's all-reduces under the global jit
    # inherit the op_name of the backward operation they complete
    assert all(profiler.scope_of("", kind)[0] == "sync" for _, kind in reduces)
    assert all(profiler.scope_of(op, kind)[0] == "sync" for op, kind in reduces)
    if builder == "shardmap":
        assert all("hvd.sync" in op for op, _ in reduces), reduces
        # the sub-scopes, before the compiler combines the all-reduces
        # (a combined one keeps a single op_name)
        subs = set(re.findall(
            r'loc\("[^"]*hvd\.sync/(\w+)/hvd\.allreduce/psum"',
            lowered.as_text(debug_info=True)))
        assert subs == {"grads", "loss"} | (
            {"stats"} if family == "batchnorm" else set())


def test_fsdp_step_scopes_gather_and_phases(hvd):
    from horovod_tpu import training

    model = _bn_mlp()
    n = hvd.size()
    xs = np.random.RandomState(0).rand(2 * n, 6).astype(np.float32)
    ys = np.random.RandomState(1).randint(0, 4, 2 * n)
    params, stats = training.init_model(model, jax.random.PRNGKey(0), xs[:1])
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), shard_params=True)
    step = training.make_shardmap_train_step(
        model, tx, loss_fn=training.softmax_xent, shard_params=True,
        instrument=False, donate=False)
    fp = training.fsdp_shard_params(hvd.fsdp_pack_params(params))
    args = (fp, training.replicate(stats), tx.init(fp),
            training.shard_batch(xs), training.shard_batch(ys))
    table = profiler._parse_hlo(step.lower(*args).compile().as_text())
    assert {"forward", "backward", "optimizer", "sync"} <= _phases(table)
    assert any("hvd.sync/params" in op for op, _ in table.values())
    gathers = [(op, kind) for op, kind in table.values()
               if kind.startswith(("all-gather", "reduce-scatter"))]
    assert gathers and all("hvd.sync" in op for op, _ in gathers), gathers


def test_scope_table_holds_the_live_step(hvd):
    step, args = _built(hvd, "shardmap", "batchnorm")
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    module = re.match(r"HloModule (\S+?),", text).group(1)
    want = {m.group(1) for m in map(
        re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = ").match, text.splitlines())
        if m}
    assert len(want) > 20
    tables = profiler.scope_table()
    mine = [t for key, t in tables.items()
            if key == module or key.startswith(module + "#")]
    assert any(set(t) == want for t in mine), (module, sorted(tables))
    table = next(t for t in mine if set(t) == want)
    assert {"forward", "backward", "optimizer", "sync"} <= _phases(table)
    # every entry is (op_name, HLO kind); the kinds are HLO's own
    kinds = {kind for _, kind in table.values()}
    assert {"parameter", "fusion"} <= kinds and "" not in kinds, kinds


@pytest.mark.parametrize("op_name,kind,want", [
    (_FWD + "/block0/attn/dot_general", "fusion", ("forward", None)),
    (_BWD + "/block0/attn/dot_general", "fusion", ("backward", None)),
    (_FWD + "/hvd.flash_fwd/hvd_flash_fwd/pallas_call", "custom-call",
     ("forward", "hvd_flash_fwd")),
    (_BWD + "/hvd.flash_bwd/while/body/closed_call/mul", "fusion",
     ("backward", "hvd.flash_bwd")),
    # the Pallas backward's calls carry no name of their own: kernel and
    # glue are both keyed by the scope flash_bwd_roofline.train reads
    (_BWD + "/hvd.flash_bwd/pallas_call", "custom-call",
     ("backward", "hvd.flash_bwd")),
    (_BWD + "/hvd.flash_bwd/transpose", "fusion",
     ("backward", "hvd.flash_bwd")),
    # a Gated DeltaNet layer's work between its projections, and the scan
    # over its chunks, in both passes (gdn_ms.train keys on it)
    (_FWD + "/block0/hvd.gdn/while/body/dot_general", "fusion",
     ("forward", "hvd.gdn")),
    (_BWD + "/block0/hvd.gdn/while/body/transpose", "fusion",
     ("backward", "hvd.gdn")),
    (_FWD + "/block0/hvd.gdn/while", "while", ("forward", "hvd.gdn")),
    # a Mamba-2 layer's work between its projections, in both passes and,
    # under jax.checkpoint, recomputed in the backward (ssm_ms.train keys
    # on it)
    (_FWD + "/block1/hvd.ssm/dot_general", "fusion", ("forward", "hvd.ssm")),
    (_BWD + "/block1/hvd.ssm/transpose", "fusion", ("backward", "hvd.ssm")),
    (_BWD + "/checkpoint/rematted_computation/block1/hvd.ssm/exp", "fusion",
     ("backward", "hvd.ssm")),
    # a latent routed layer's projections (moe_latent_ms.train), and a
    # relu² expert's call keyed apart from the scope around it
    (_FWD + "/block0/hvd.moe_latent/dot_general", "fusion",
     ("forward", "hvd.moe_latent")),
    (_BWD + "/block0/hvd.moe_experts/hvd_moe_relu2_bwd/pallas_call",
     "custom-call", ("backward", "hvd_moe_relu2_bwd")),
    ("jit(step)/hvd.optimizer/mul", "fusion", ("optimizer", None)),
    ("jit(step)/hvd.optimizer/hvd_fused_adam/pallas_call", "custom-call",
     ("optimizer", "hvd_fused_adam")),
    # the wrapper's exchange sits inside tx.update: sync wins
    ("jit(step)/hvd.optimizer/hvd.sync/grads/hvd.allreduce/div", "fusion",
     ("sync", None)),
    # a bucketed exchange issued from inside the backward
    (_BWD + "/hvd.sync/grads/hvd.allreduce/psum", "all-reduce",
     ("sync", None)),
    # the partitioner's all-reduce carries a backward op_name
    (_BWD + "/dot_general", "all-reduce-start", ("sync", None)),
    (_FWD + "/dot_general", "all-gather", ("sync", None)),
    ("jit(step)/hvd.sync/grads/hvd_quantize/pallas_call", "custom-call",
     ("sync", "hvd_quantize")),
    # rematerialized forward under jax.checkpoint runs in the backward
    ("jit(fsdp_step)/shard_map/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/hvd.forward/Dense_0/dot_general", "fusion",
     ("backward", None)),
    ("jit(fsdp_step)/shard_map/transpose(jvp(jvp()))/checkpoint/hvd.forward/"
     "Dense_0/transpose", "fusion", ("backward", None)),
    ("jit(fsdp_step)/shard_map/jvp(hvd.forward)/Dense_0/dot_general",
     "fusion", ("forward", None)),
    ("jit(step)/hvd.allreduce/mine/psum", "fusion", (None, None)),
    ("jit(broadcast_in_dim)/broadcast_in_dim", "broadcast", (None, None)),
    ("", "copy", (None, None)),
])
def test_scope_of_precedence(op_name, kind, want):
    assert profiler.scope_of(op_name, kind) == want


def test_gated_delta_mixer_runs_under_its_scope_in_both_passes():
    """``hvd.gdn`` is the innermost ``hvd.*`` scope of everything between a
    Gated DeltaNet layer's in- and out-projections — the convolution, the
    norms, the decays and the scan over the chunks — forward and in the
    transposed pass, and of neither projection."""
    from horovod_tpu import models
    from horovod_tpu.models.transformer import TransformerBlock

    block = TransformerBlock(**models.TransformerLM(
        vocab=8, dim=64, depth=1, heads=1, pos_embedding="rope",
        layers=(models.Layer(mixer=models.GatedDelta(1, 2, 16, 16), ffn=1),),
        dtype=jnp.float32).block_config(0))
    x = jnp.zeros((1, 80, 64))
    pos = jnp.arange(80)[None]
    params = block.init(jax.random.PRNGKey(0), x, positions=pos)["params"]

    @jax.named_scope("hvd.forward")
    def loss(p):
        return block.apply({"params": p}, x, positions=pos).sum()

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*hvd\.gdn[^"]*)"', text))
    scopes = {profiler.scope_of(n) for n in names}
    assert {("forward", "hvd.gdn"), ("backward", "hvd.gdn")} <= scopes
    assert {k for _, k in scopes} == {"hvd.gdn"}
    assert any("while" in n and "transpose(" in n for n in names)
    assert any("while" in n and "transpose(" not in n for n in names)
    projections = set(re.findall(
        r'loc\("([^"]*(?:in_proj_qkvz|out_proj)[^"]*dot_general)"', text))
    assert projections and not any("hvd.gdn" in n for n in projections)


def test_mamba2_mixer_runs_under_its_scope_in_both_passes():
    """``hvd.ssm`` is the innermost ``hvd.*`` scope of everything between a
    Mamba-2 layer's in- and out-projections — the convolution, the step
    sizes, the chunked recurrence and the gated norm — forward and in the
    transposed pass, and of neither projection."""
    from horovod_tpu import models
    from horovod_tpu.models.transformer import TransformerBlock

    block = TransformerBlock(**models.TransformerLM(
        vocab=8, dim=64, depth=1, heads=1, pos_embedding="none",
        layers=(models.Layer(mixer=models.Mamba2(4, 8, 2, 16, chunk=16),
                             ffn=None),),
        dtype=jnp.float32).block_config(0))
    x = jnp.zeros((1, 80, 64))
    params = block.init(jax.random.PRNGKey(0), x)["params"]

    @jax.named_scope("hvd.forward")
    def loss(p):
        return block.apply({"params": p}, x).sum()

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*hvd\.ssm[^"]*)"', text))
    scopes = {profiler.scope_of(n) for n in names}
    assert {("forward", "hvd.ssm"), ("backward", "hvd.ssm")} <= scopes
    assert {k for _, k in scopes} == {"hvd.ssm"}
    projections = set(re.findall(
        r'loc\("([^"]*(?:in_proj|out_proj)[^"]*dot_general)"', text))
    assert projections and not any("hvd.ssm" in n for n in projections)


@pytest.mark.parametrize("builder,module", [
    ("jit", "jit_hvd1_step"), ("shardmap", "jit_hvd1_shard_step")])
def test_scope_scheme_rides_the_module_name(hvd, builder, module):
    """JAX leaves metadata out of the persistent compile cache's key, so a
    scope-only edit would load an executable with the old names; the module
    name is in the key, and carries the scheme's version
    (``training._SCOPES``: bump it with the scopes)."""
    step, args = _built(hvd, builder, "batchnorm")
    text = step.lower(*args).compile().as_text()
    assert re.match(r"HloModule (\S+?),", text).group(1) == module


def test_user_collective_under_jit_is_named(hvd):
    from horovod_tpu.ops.collective import _smap
    from jax.sharding import PartitionSpec as P

    def per_shard(x):
        y = hvd.allreduce(x, hvd.Sum, name="mine")
        return hvd.allgather(y) + hvd.broadcast(x, 0).sum()

    fn = jax.jit(_smap(per_shard, hvd.mesh(), (P("data"),), P()))
    x = jnp.ones((hvd.size(), 4))
    table = profiler._parse_hlo(fn.lower(x).compile().as_text())
    ops = " ".join(op for op, _ in table.values())
    assert "hvd.allreduce/mine" in ops
    assert "hvd.allgather" in ops and "hvd.broadcast" in ops
    # eager calls take the same entry points unscoped and unchanged
    assert float(hvd.allreduce(jnp.ones(()), hvd.Sum, name="e")) == hvd.size()


def test_instrumented_step_writes_host_spans(hvd, tmp_path):
    """``hvd.step`` around ``hvd.step/dispatch`` per call, ``hvd.shard_batch``
    per placed array: TraceMes on the profiler's clock, read back the way the
    benchmark reads them."""
    sys.path.insert(0, _ROOT)
    from benchmarks import scope_reduce
    from horovod_tpu import training

    step, args = _built(hvd, "jit", "batchnorm")
    step = training.instrument_step(step, batch_arg=3)
    xs = np.asarray(args[3])
    state = list(args[:3])
    *state, _ = step(*state, *args[3:])          # compile outside the trace
    with profiler.timeline(str(tmp_path)):
        for _ in range(3):
            batch = training.shard_batch(xs)
            *state, loss = step(*state, batch, args[4])
        float(loss)
    host = scope_reduce.load_xplane(str(tmp_path))["host"]
    names = [n for n, _, _ in host]
    assert names.count("hvd.step") == 3
    assert names.count("hvd.step/dispatch") == 3
    assert names.count("hvd.shard_batch") == 3
    per_step = scope_reduce.host_per_step(host)
    assert len(per_step) == 3
    assert all(0 <= s["hooks"] < s["step"] for s in per_step)
    assert all(s["feed"] > 0 for s in per_step)


def test_scope_reduce_matches_its_hand_made_trace():
    sys.path.insert(0, _ROOT)
    from benchmarks import scope_reduce

    with open(os.path.join(_ROOT, "benchmarks", "scope_sample.json")) as f:
        sample = json.load(f)
    scope_reduce.self_check(sample)
    # a wrong answer is caught, not waved through
    sample["expect"]["phase.backward"] *= 1.01
    with pytest.raises(SystemExit, match="phase.backward"):
        scope_reduce.self_check(sample)
    # a program with no table for the trace's module reads nothing
    assert scope_reduce.reduce(
        sample["trace"], {"jit_other": {}}, profiler.scope_of) is None


def test_scope_reduce_accounts_for_all_busy_time():
    sys.path.insert(0, _ROOT)
    from benchmarks import scope_reduce

    with open(os.path.join(_ROOT, "benchmarks", "scope_sample.json")) as f:
        sample = json.load(f)
    red = scope_reduce.reduce(
        sample["trace"], scope_reduce.tables_from_json(sample["table"]),
        profiler.scope_of)
    assert sum(red["phases_s"].values()) + red["unattributed_s"] == \
        pytest.approx(red["busy_s"], rel=1e-12)
    assert red["unattributed_kinds"] == [["not in the table", 2e-08]]


def test_benchmark_manifest_check_passes():
    """``run.py --check``: the manifest, every cell's files and every
    per-layer metric's reader (ROADMAP D11, the part that keeps this
    manifest honest)."""
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "run.py"),
         "--check"], cwd=_ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "check ok: 7 cell(s)" in out.stdout
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    new = ["phase_forward_ms.train", "phase_backward_ms.train",
           "phase_optimizer_ms.train", "scope_unattributed_share.train",
           "flash_bwd_roofline.train", "grad_sync_hidden_share.train",
           "host_feed_ms_p50.train"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n].get("workloads") for n in new)
