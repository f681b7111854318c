"""The system under test for a decoder configuration: ``DecoderLM`` served through
``ModelRegistry.register(..., generate=...)``, driven in process through
``version.batcher.submit_generate`` with the benchmark's own weights from the seed."""
import functools
import gc

import jax

from chipbench.reference import decoder as ref


def _program_layout(cfg, key):
    s = ref.generate(cfg, key)
    p = {"embed": {"tok": s["tok"], "pos": s["pos"]},
         "head": {"ln_g": s["ln_g"], "ln_b": s["ln_b"], "w": s["head"]}}
    for i in range(cfg["n_layers"]):
        p[f"layer_{i}"] = {k: s[k][i] for k in ref.LAYER_KEYS}
    return p


class Program:
    def __init__(self, cfg, mix, seed, chips):
        from deeplearning4j_tpu.models.decoder import DecoderConfig, DecoderLM
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        model = DecoderLM(DecoderConfig(
            vocab_size=cfg["vocab_size"], n_layers=cfg["n_layers"],
            n_heads=cfg["n_heads"], d_model=cfg["d_model"], d_ff=cfg["d_ff"],
            max_len=cfg["max_len"], eos_id=cfg["vocab_size"]))
        model.params = jax.jit(functools.partial(_program_layout, cfg))(ref.key_of(seed))
        eng = dict(cfg["engine"])
        eng["prompt_buckets"] = tuple(eng["prompt_buckets"])
        eng["decode_buckets"] = tuple(eng["decode_buckets"])
        self.registry = ModelRegistry()
        self.version = self.registry.register("lm", model, generate=eng)
        self.pool = self.version.batcher.engine.pool

    def submit(self, prompt, max_tokens):
        """The call ``InferenceServer._generate_traced`` makes; greedy, no deadline."""
        return self.version.batcher.submit_generate(prompt, max_tokens)

    def compiles_in_window(self):
        return self.version.retraces_since_warmup()

    def close(self):
        self.registry.shutdown()
        self.version.batcher.engine.params = None
        self.version.model.params = None
        self.pool.k = self.pool.v = None
        self.registry = self.version = self.pool = None
        gc.collect()


def build(cfg, mix, seed, chips):
    return Program(cfg, mix, seed, chips)
