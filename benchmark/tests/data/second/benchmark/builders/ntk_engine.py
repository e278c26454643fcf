"""Builder of the test-only second configuration: what the engine
builder does, with the program's config made from this architecture's
own keys. The program knows one rotary base; this architecture scales
its published base by ``rope_scaling.factor`` (the "NTK-aware" change
of base, ``theta * factor ** (d / (d - 2))``), so the builder hands the
program the scaled base."""

from benchmark.builders._decoder import decoder_config
from benchmark.builders.engine import System as EngineSystem


class System(EngineSystem):
    def program_config(self, name: str):
        scaling, dh = self.dims["rope_scaling"], self.dims["head_dim"]
        if scaling["rope_type"] != "ntk_base" or set(
                self.dims["layer_types"]) != {"full_attention"}:
            raise ValueError(f"{name}: not a shape this builder serves")
        base = self.dims["rope_theta"] * scaling["factor"] ** (
            dh / (dh - 2))
        return decoder_config(dict(self.dims, rope_theta=base), name)
