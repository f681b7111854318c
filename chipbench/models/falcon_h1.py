"""The system under test for a Falcon-H1 configuration: ``FalconH1LM`` served through
``ModelRegistry.register(..., generate=...)`` and the one ``DecodeEngine``, driven in
process through ``version.batcher.submit_generate`` with the benchmark's own weights
from the seed (the same arrays the plain reference makes)."""
from chipbench.models import decoder
from chipbench.reference import falcon_h1 as ref


def program_layout(s):
    """The reference's weights in the program's two-level layout (no copy)."""
    p = {"embed": {"tok": s["embed"]}, "head": {"norm": s["final_norm"], "w": s["head"]}}
    for i, layer in enumerate(s["layers"]):
        p[f"layer_{i}"] = layer
    return p


class Program(decoder.Program):
    def __init__(self, cfg, mix, seed, chips):
        from deeplearning4j_tpu.models.falcon_h1 import FalconH1Config, FalconH1LM
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        model = FalconH1LM(FalconH1Config.from_published(
            cfg, max_len=cfg["max_len"], eos_id=cfg["vocab_size"]))
        model.params = program_layout(ref.make_params(cfg, seed))
        eng = dict(cfg["engine"])
        eng["prompt_buckets"] = tuple(eng["prompt_buckets"])
        eng["decode_buckets"] = tuple(eng["decode_buckets"])
        self.registry = ModelRegistry()
        self.version = self.registry.register("lm", model, generate=eng)
        self.pool = self.version.batcher.engine.pool

    def close(self):
        self.pool.state = {}
        super().close()


def build(cfg, mix, seed, chips):
    return Program(cfg, mix, seed, chips)
