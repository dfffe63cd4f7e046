"""HF → native bridge: logit equivalence against transformers eager models.

These are the strongest correctness oracles for the native model families:
the same weights must produce (near-)identical logits.
"""

import jax.numpy as jnp
import numpy as np
import pytest
import torch

from torchdistx_tpu.models import convert, gpt2, llama


def _np_state_dict(model):
    return {k: v.detach().numpy() for k, v in model.state_dict().items()}


class TestGPT2:
    @pytest.fixture(scope="class")
    def hf(self):
        from transformers import GPT2Config, GPT2LMHeadModel

        torch.manual_seed(0)
        config = GPT2Config(
            vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4
        )
        model = GPT2LMHeadModel(config).eval()
        return model, config

    def test_logit_equivalence(self, hf):
        model, config = hf
        cfg = convert.gpt2_config_from_hf(
            config, dtype=jnp.float32, remat=False
        )
        params = convert.gpt2_params_from_hf(_np_state_dict(model), cfg)
        tokens = torch.randint(0, 128, (2, 16), generator=torch.Generator().manual_seed(1))
        with torch.no_grad():
            ref = model(tokens).logits.numpy()
        ours = np.asarray(
            gpt2.forward(params, jnp.asarray(tokens.numpy()), cfg, attn_impl="jnp")
        )
        assert np.abs(ref - ours).max() < 2e-3

    def test_from_materialized_arrays(self, hf):
        """deferred_init(HF) → materialize_module_jax → convert → forward."""
        from transformers import GPT2Config, GPT2LMHeadModel

        import torchdistx_tpu.deferred_init as di
        from torchdistx_tpu.materialize import materialize_module_jax

        config = GPT2Config(
            vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4
        )
        fake = di.deferred_init(GPT2LMHeadModel, config)
        arrays = materialize_module_jax(fake)
        cfg = convert.gpt2_config_from_hf(config, dtype=jnp.float32, remat=False)
        params = convert.gpt2_params_from_hf(arrays, cfg)
        logits = gpt2.forward(
            params, jnp.zeros((1, 8), jnp.int32), cfg, attn_impl="jnp"
        )
        assert logits.shape == (1, 8, 128)
        assert bool(jnp.isfinite(logits).all())


class TestLlama:
    @pytest.fixture(scope="class")
    def hf(self):
        from transformers import LlamaConfig, LlamaForCausalLM

        torch.manual_seed(0)
        config = LlamaConfig(
            vocab_size=128,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=64,
            attn_implementation="eager",
        )
        model = LlamaForCausalLM(config).eval()
        return model, config

    def test_logit_equivalence(self, hf):
        model, config = hf
        cfg = convert.llama_config_from_hf(
            config, dtype=jnp.float32, remat=False
        )
        params = convert.llama_params_from_hf(_np_state_dict(model), cfg)
        tokens = torch.randint(0, 128, (2, 16), generator=torch.Generator().manual_seed(1))
        with torch.no_grad():
            ref = model(tokens).logits.numpy()
        ours = np.asarray(
            llama.forward(params, jnp.asarray(tokens.numpy()), cfg, attn_impl="jnp")
        )
        assert np.abs(ref - ours).max() < 2e-3

    def test_generate_with_converted_weights(self, hf):
        from torchdistx_tpu.models.generate import generate
        import jax

        model, config = hf
        cfg = convert.llama_config_from_hf(config, dtype=jnp.float32, remat=False)
        params = convert.llama_params_from_hf(_np_state_dict(model), cfg)
        prompt = jnp.zeros((1, 4), jnp.int32)
        out = generate(
            params, prompt, jax.random.PRNGKey(0), model=llama, cfg=cfg,
            max_new_tokens=4, temperature=0.0,
        )
        # HF greedy reference
        with torch.no_grad():
            hf_out = model.generate(
                torch.zeros((1, 4), dtype=torch.long), max_new_tokens=4,
                do_sample=False,
            )[0, 4:].numpy()
        assert np.array_equal(np.asarray(out)[0], hf_out)


class TestAfmoe:
    """The published AFMoE names (``models/afmoe_torch.py``) -> the stacked
    layout -> the published names: every key accounted for, an absent
    expert's key never created."""

    def _module(self, first, held):
        from torchdistx_tpu.models import afmoe_torch

        torch.manual_seed(0)
        config = afmoe_torch.AfmoeConfig(
            vocab_size=64, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_hidden_layers=4, num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            num_experts=8, num_experts_per_tok=2, sliding_window=8,
        )
        module = afmoe_torch.AfmoeForCausalLM(config)
        for layer in module.model.layers[config.num_dense_layers:]:
            del layer.mlp.experts[first + held:]
            del layer.mlp.experts[:first]
        return module, config

    @pytest.mark.parametrize("first,held", [(0, 8), (2, 4)])
    def test_round_trip_accounts_for_every_published_key(self, first, held):
        from torchdistx_tpu.models import afmoe

        module, config = self._module(first, held)
        state = _np_state_dict(module)
        cfg = afmoe.AfmoeConfig(
            vocab_size=64, dim=32, n_dense_layers=1, n_moe_layers=3, n_heads=4,
            n_kv_heads=2, head_dim=8, ffn_dim=48, expert_dim=16, shared_dim=16,
            n_experts=8, experts_per_token=2, n_experts_held=held,
            first_expert_held=first, window=8, dtype=jnp.float32,
        )
        assert cfg.layer_types == config.layer_types
        params = convert.afmoe_params_from_hf(state, cfg)
        assert {
            k: v.shape for k, v in params["moe_layers"].items()
        }.items() >= {"e_gate": (3, held, 32, 16), "wg": (3, 32, 32)}.items()
        back = convert.afmoe_params_to_hf(params, cfg)
        assert set(back) == set(state)
        for name, a in state.items():
            assert np.array_equal(np.asarray(back[name]), a), name
        assert not any(f"experts.{held}." in name for name in back)
        # every leaf of the stacked layout came from some published key
        n = sum(a.size for a in state.values())
        assert n == afmoe.num_params(cfg)
