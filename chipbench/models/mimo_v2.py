"""The system under test for a MiMo-V2 configuration: ``MiMoV2LM`` served through
``ModelRegistry.register(..., generate=...)`` and the one ``DecodeEngine``, driven in
process through ``version.batcher.submit_generate`` with the benchmark's own weights from
the seed (the same arrays the plain reference makes)."""
from chipbench.models import decoder
from chipbench.reference import mimo_v2 as ref


def program_layout(s):
    """The reference's weights in the program's two-level layout: no copy."""
    p = {"embed": {"tok": s["embed"]}, "head": {"norm": s["final"], "w": s["head"]}}
    p.update({f"layer_{i}": layer for i, layer in enumerate(s["layers"])})
    return p


class Program(decoder.Program):
    def __init__(self, cfg, mix, seed, chips):
        from deeplearning4j_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2LM
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        model = MiMoV2LM(MiMoV2Config.from_published(
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
