"""Model + parallel layer tests on the 8-device virtual CPU mesh:
sharded init, train-step convergence, decode-cache equivalence, and the
full multi-axis (fsdp, seq, tensor) dryrun."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.llama import (
    LlamaConfig, init_llama, llama_decode, llama_forward, llama_loss,
    llama_logical_axes)
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import logical_to_spec, param_shardings
from ray_tpu.parallel.train_step import (
    TrainState, create_train_state, make_train_step)


class TestMesh:
    def test_resolve_wildcard(self):
        assert MeshConfig(data=-1, fsdp=2).resolve(8)["data"] == 4

    def test_resolve_mismatch(self):
        with pytest.raises(ValueError):
            MeshConfig(data=3, fsdp=2).resolve(8)

    def test_create(self):
        mesh = create_mesh(MeshConfig(data=-1, fsdp=2, tensor=2))
        assert mesh.shape["data"] == 2
        assert mesh.shape["fsdp"] == 2


class TestShardingRules:
    def test_logical_to_spec(self):
        spec = logical_to_spec(("embed", "mlp"))
        assert spec == jax.sharding.PartitionSpec("fsdp", "tensor")

    def test_duplicate_axis_replicates(self):
        spec = logical_to_spec(("mlp", "mlp"))
        assert spec[0] == "tensor" and spec[1] is None

    def test_batch_tuple(self):
        spec = logical_to_spec(("batch", "seq"))
        assert spec[0] == ("data", "fsdp")


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


class TestWhatARematPolicyKeeps:
    """`remat_policy` says what the backward keeps and never what it
    computes: under "dots" every matmul's output is kept, the flash
    forward's (a `pallas_call`) and the head's too, so neither runs a
    second time; under "full" both do."""

    @pytest.fixture(scope="class")
    def model(self):
        cfg = dataclasses.replace(
            LlamaConfig.debug_1l(), num_layers=2, vocab_size=384,
            dtype=jnp.float32, attn_impl="flash", loss_chunk=64)
        params = init_llama(cfg, jax.random.key(0))
        tok = jax.random.randint(jax.random.key(1), (2, 129), 0,
                                 cfg.vocab_size)
        return cfg, params, {"inputs": tok[:, :-1], "targets": tok[:, 1:]}

    @pytest.fixture(scope="class")
    def unrematted(self, model):
        cfg, params, batch = model
        return jax.value_and_grad(llama_loss)(params, batch, cfg)

    # a scan's body is traced once for all its layers: a run of layers
    # under one policy holds the flash forward, dq and dk/dv once, and
    # "mixed:1" cuts the two layers into a run a policy. The head's three
    # matmuls are the logits, dX and dW.
    @pytest.mark.parametrize("policy, flash_calls, head_dots", [
        ("dots", 3, 3), ("full", 4, 4), ("mixed:1", 3 + 4, 3)])
    def test_the_backward_runs_no_kept_matmul_again(
            self, model, unrematted, policy, flash_calls, head_dots):
        cfg, params, batch = model
        cfg = dataclasses.replace(cfg, remat=True, remat_policy=policy)
        grad = jax.value_and_grad(llama_loss)
        eqns = list(_equations(
            jax.make_jaxpr(lambda p: grad(p, batch, cfg))(params).jaxpr))
        assert sum(e.primitive.name == "pallas_call"
                   for e in eqns) == flash_calls

        def of_the_head(eqn):
            # logits, dX or dW: the only matmuls with the vocabulary in them
            return any(cfg.vocab_size in v.aval.shape
                       for v in (*eqn.invars, *eqn.outvars))

        assert sum(e.primitive.name == "dot_general" and of_the_head(e)
                   for e in eqns) == head_dots
        loss, grads = grad(params, batch, cfg)
        want_loss, want = unrematted
        assert float(loss) == float(want_loss)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)


class TestLlama:
    def test_mixed_remat_matches_full(self):
        """remat_policy='mixed:K' (first K layers keep matmul outputs,
        rest recompute) must produce the same loss and gradients as
        'full' — the policy only changes what is stored, never the math."""
        import dataclasses

        from ray_tpu.models.llama import llama_loss

        cfg = dataclasses.replace(LlamaConfig.debug_1l(), num_layers=2,
                                  max_seq_len=32)
        params = init_llama(dataclasses.replace(cfg, remat=False),
                            jax.random.key(0))
        tok = jax.random.randint(jax.random.key(1), (2, 17), 0,
                                 cfg.vocab_size)
        batch = {"inputs": tok[:, :-1], "targets": tok[:, 1:]}
        results = {}
        for pol in ("full", "mixed:1"):
            c = dataclasses.replace(cfg, remat=True, remat_policy=pol)
            results[pol] = jax.value_and_grad(
                lambda p, c=c: llama_loss(p, batch, c))(params)
        (ref_loss, ref_grads), (loss, grads) = \
            results["full"], results["mixed:1"]
        assert abs(float(loss) - float(ref_loss)) < 1e-5
        for a, b in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(ref_grads)):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4

    def test_forward_shape(self):
        cfg = LlamaConfig.debug_1l()
        params = init_llama(cfg, jax.random.key(0))
        logits = llama_forward(params, jnp.zeros((2, 16), jnp.int32), cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_decode_cache_matches_full(self):
        """Prefill+decode with kv cache == one full forward."""
        cfg = LlamaConfig.debug_1l()
        params = init_llama(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (1, 12), 0,
                                    cfg.vocab_size)
        full = llama_forward(params, tokens, cfg)

        B, prefill = 1, 8
        caches = [
            (jnp.zeros((B, 16, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
             jnp.zeros((B, 16, cfg.num_kv_heads, cfg.head_dim), cfg.dtype))
            for _ in range(cfg.num_layers)]
        logits, caches = llama_decode(
            params, tokens[:, :prefill], cfg, caches, jnp.int32(0))
        np.testing.assert_allclose(
            logits, full[:, :prefill], atol=3e-2, rtol=3e-2)
        for t in range(prefill, 12):
            pos = jnp.full((1, 1), t, jnp.int32)
            logits, caches = llama_decode(
                params, tokens[:, t:t + 1], cfg, caches, jnp.int32(t),
                positions=pos)
            np.testing.assert_allclose(
                logits[:, 0], full[:, t], atol=3e-2, rtol=3e-2)

    def test_a_pattern_of_conv_and_attention_layers(self):
        """A dense model with a layer pattern and its own head: the tree
        goes by kind, the runs are scanned in order under every remat
        policy, and prefill + decode through both kinds of state is the
        full forward."""
        from ray_tpu.models.llama import init_decode_state

        cfg = dataclasses.replace(
            LlamaConfig.tiny(), num_layers=5, dtype=jnp.float32,
            layer_types=("conv", "conv", "full_attention", "conv",
                         "full_attention"))
        assert cfg.layer_runs() == (
            ("conv_dense", 0, 2), ("attention_dense", 0, 1),
            ("conv_dense", 2, 1), ("attention_dense", 1, 1))
        params = init_llama(cfg, jax.random.key(0))
        assert sorted(params["layers"]) == ["attention_dense", "conv_dense"]
        assert params["layers"]["conv_dense"]["conv_w"].shape == (3, 128, 3)
        assert "lm_head" in params
        n = sum(x.size for x in jax.tree.leaves(params))
        assert n == cfg.num_params()
        tokens = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                    cfg.vocab_size)
        full = llama_forward(params, tokens, cfg)
        for policy in ("dots", "full", "mixed:1", "mixed:3"):
            c = dataclasses.replace(cfg, remat=True, remat_policy=policy)
            np.testing.assert_allclose(llama_forward(params, tokens, c),
                                       full, atol=1e-5, rtol=1e-5)
        state = init_decode_state(cfg, 2, 16)
        logits, state = llama_decode(params, tokens[:, :8], cfg, state,
                                     jnp.int32(0))
        np.testing.assert_allclose(logits, full[:, :8], atol=1e-4, rtol=1e-4)
        for t in range(8, 12):
            logits, state = llama_decode(params, tokens[:, t:t + 1], cfg,
                                         state, jnp.int32(t))
            np.testing.assert_allclose(logits[:, 0], full[:, t], atol=1e-4,
                                       rtol=1e-4)

    def test_param_count(self):
        cfg = LlamaConfig.tiny()
        params = init_llama(cfg, jax.random.key(0))
        n = sum(x.size for x in jax.tree.leaves(params))
        assert n == cfg.num_params()


class TestTrainStep:
    def _setup(self, mesh_cfg, llama_cfg=None, accum=1):
        cfg = llama_cfg or LlamaConfig.tiny(vocab_size=64)
        mesh = create_mesh(mesh_cfg)
        tx = optax.adamw(3e-3)
        with jax.set_mesh(mesh):
            state, sh = create_train_state(
                lambda k: init_llama(cfg, k), tx, mesh,
                llama_logical_axes(cfg))
            step = make_train_step(
                lambda p, b: llama_loss(p, b, cfg), tx, mesh, sh,
                batch_logical_axes=("batch", "seq"), grad_accum=accum)
        return cfg, mesh, state, step

    def test_loss_decreases_fsdp_tensor(self):
        cfg, mesh, state, step = self._setup(
            MeshConfig(data=-1, fsdp=2, tensor=2))
        rng = np.random.default_rng(0)
        tok = rng.integers(0, 64, (8, 33), dtype=np.int32)
        batch = {"inputs": tok[:, :-1], "targets": tok[:, 1:]}
        with jax.set_mesh(mesh):
            losses = []
            for _ in range(5):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_grad_accum_matches(self):
        """accum=2 over 8 == accum=1 over same 8 (same update math)."""
        cfg, mesh, s1, step1 = self._setup(MeshConfig(data=-1))
        _, _, s2, step2 = self._setup(MeshConfig(data=-1), accum=2)
        rng = np.random.default_rng(1)
        tok = rng.integers(0, 64, (8, 17), dtype=np.int32)
        batch = {"inputs": tok[:, :-1], "targets": tok[:, 1:]}
        with jax.set_mesh(create_mesh(MeshConfig(data=-1))):
            _, m1 = step1(s1, batch)
            _, m2 = step2(s2, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)

    def test_params_sharded(self):
        cfg, mesh, state, _ = self._setup(MeshConfig(data=-1, fsdp=4))
        wq = state.params["layers"]["wq"]
        # embed dim sharded over fsdp=4
        assert wq.sharding.spec[1] == "fsdp"


class TestGraftEntry:
    def test_entry(self):
        import __graft_entry__ as g

        fn, args = g.entry()
        out = jax.jit(fn)(*args)
        assert out.shape[-1] == 256

    def test_dryrun_multichip(self):
        import __graft_entry__ as g

        g.dryrun_multichip(8)


# ----------------------------------------------------------------- MoE / EP
class TestMoEExpertParallel:
    """A model with experts is a ``LlamaConfig`` with ``num_experts`` above
    0: the block, the loss and the train step are the dense model's, the
    feed-forward half is ``models/moe.py``'s."""

    @staticmethod
    def _cfg():
        import dataclasses

        return dataclasses.replace(
            LlamaConfig.tiny(), hidden=64, mlp_hidden=128, num_heads=4,
            num_kv_heads=4, head_dim=16, num_experts=4, experts_per_token=2,
            qk_norm=True, router_aux_loss_coef=0.01)

    def test_forward_shapes_and_finite_aux(self):
        import dataclasses

        import jax
        import numpy as np

        from ray_tpu.models.llama import llama_forward, llama_loss

        cfg = self._cfg()
        params = init_llama(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 17), 0,
                                    cfg.vocab_size)
        logits = llama_forward(params, tokens[:, :-1], cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()
        # the load-balancing term is positive and rides on the loss
        with_aux = llama_loss(params, {"tokens": tokens}, cfg)
        without = llama_loss(params, {"tokens": tokens}, dataclasses.replace(
            cfg, router_aux_loss_coef=0.0))
        assert float(with_aux) > float(without)

    def test_expert_parallel_training_step(self):
        """Full train step on a (data=2, expert=4) mesh: the expert dim of
        the FFN stacks shards over the EP axis; loss must decrease."""
        import jax
        import numpy as np
        import optax

        from ray_tpu.models.llama import llama_logical_axes, llama_loss
        from ray_tpu.parallel.mesh import MeshConfig, create_mesh
        from ray_tpu.parallel.train_step import (
            create_train_state, make_train_step)

        cfg = self._cfg()
        mesh = create_mesh(MeshConfig(data=2, fsdp=1, expert=4))
        tx = optax.adamw(1e-3)
        with jax.set_mesh(mesh):
            state, shardings = create_train_state(
                lambda k: init_llama(cfg, k), tx, mesh,
                llama_logical_axes(cfg))
            step = make_train_step(
                lambda p, b: llama_loss(p, b, cfg), tx, mesh, shardings,
                batch_logical_axes=("batch", "seq"))
            toks = np.random.default_rng(0).integers(
                0, cfg.vocab_size, (8, 17)).astype(np.int32)
            batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
            losses = []
            for _ in range(3):
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        # expert weights really sharded over the expert axis: the stacked
        # we_gate is (L, E, h, m) — dim 1 is the expert dim
        sh = state.params["layers"]["we_gate"].sharding
        assert sh.spec[1] == "expert", sh.spec


class TestLora:
    """Frozen-base LoRA (VERDICT r2 item 3): adapters start at identity,
    train under a frozen base, and merge back exactly."""

    def _setup(self, targets=None, dtype=None):
        import dataclasses as dc

        from ray_tpu.models.llama import LoraConfig, init_lora

        cfg = LlamaConfig.tiny()
        if dtype is not None:
            # fp32 activations for exactness checks: in bf16, merely adding
            # the (zero) adapter ops changes XLA fusion order by ~1 ulp
            cfg = dc.replace(cfg, dtype=dtype)
        lcfg = LoraConfig(rank=4, **(
            {"targets": targets} if targets else {}))
        base = init_llama(cfg, jax.random.key(0))
        lora = init_lora(cfg, lcfg, jax.random.key(1))
        return cfg, lcfg, base, lora

    def test_b_zero_init_is_identity(self):
        cfg, lcfg, base, lora = self._setup(dtype=jnp.float32)
        tok = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
        plain = llama_forward(base, tok, cfg)
        adapted = llama_forward(base, tok, cfg, lora=lora, lora_cfg=lcfg)
        np.testing.assert_allclose(plain, adapted, atol=1e-6)

    def test_merge_matches_activation_side(self):
        from ray_tpu.models.llama import merge_lora

        cfg, lcfg, base, lora = self._setup(dtype=jnp.float32)
        # perturb B so the adapters actually do something
        lora = jax.tree.map(
            lambda a: a + 0.05 * jax.random.normal(
                jax.random.key(2), a.shape, a.dtype), lora)
        tok = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
        act_side = llama_forward(base, tok, cfg, lora=lora, lora_cfg=lcfg)
        merged = merge_lora(base, lora, cfg, lcfg)
        merged_out = llama_forward(merged, tok, cfg)
        np.testing.assert_allclose(act_side, merged_out, rtol=0.05,
                                   atol=0.05)  # bf16 activations

    def test_lora_trains_base_frozen(self):
        from ray_tpu.models.llama import (
            LoraConfig, init_lora, llama_lora_loss, lora_logical_axes)

        cfg, lcfg, base, _ = self._setup()
        mesh = create_mesh(MeshConfig(data=-1, fsdp=2, tensor=2))
        tx = optax.adam(5e-3)
        with jax.set_mesh(mesh):
            base_sh = jax.device_put(
                base, param_shardings(llama_logical_axes(cfg), mesh))
            state, shardings = create_train_state(
                lambda k: init_lora(cfg, lcfg, k), tx, mesh,
                lora_logical_axes(cfg, lcfg), seed=1)
            step = make_train_step(
                lambda lo, b, fz: llama_lora_loss(fz, lo, b, cfg, lcfg),
                tx, mesh, shardings, batch_logical_axes=("batch", "seq"),
                frozen=base_sh,
                frozen_logical_axes=llama_logical_axes(cfg))
            rng = np.random.default_rng(0)
            tok = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)
            b = {"inputs": tok[:, :-1], "targets": tok[:, 1:]}
            losses = []
            for _ in range(8):
                state, m = step(state, b)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        # optimizer state exists only for the adapters
        n_opt = len(jax.tree.leaves(state.opt_state))
        n_lora = len(jax.tree.leaves(state.params))
        assert n_opt <= 2 * n_lora + 4, (n_opt, n_lora)

    def test_chunked_loss_matches_dense(self):
        cfg, lcfg, base, lora = self._setup()
        import dataclasses as dc

        cfg_chunked = dc.replace(cfg, loss_chunk=8)
        rng = np.random.default_rng(0)
        tok = rng.integers(0, cfg.vocab_size, (2, 17), dtype=np.int32)
        b = {"inputs": tok[:, :-1], "targets": tok[:, 1:]}
        dense = float(llama_loss(base, b, cfg))
        chunked = float(llama_loss(base, b, cfg_chunked))
        assert abs(dense - chunked) < 1e-3, (dense, chunked)
        # grads agree too (the checkpointed-scan backward path)
        gd = jax.grad(lambda p: llama_loss(p, b, cfg))(base)
        gc = jax.grad(lambda p: llama_loss(p, b, cfg_chunked))(base)
        np.testing.assert_allclose(gd["lm_head"], gc["lm_head"],
                                   rtol=2e-2, atol=2e-4)
