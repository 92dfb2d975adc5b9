"""Known-good B1: one launch path makes every family's key, and the
aggregate it appends (`self._qkey`) carries what the builders bake in;
the builder is found through the parameter it is passed as.
"""


class MiniEngine:
    def __init__(self, model, temperature):
        self.model = model
        self.temperature = temperature
        self._qkey = (("sampling", self.temperature),)
        self.programs = {}

    def _get_program(self, key, build):
        if key not in self.programs:
            self.programs[key] = build()
        return self.programs[key]

    def _launcher(self, family, dims, builder):
        return self._get_program((family,) + dims + self._qkey, builder)

    def decode(self, batch):
        return self._launcher("decode", (batch,),
                              builder=lambda: self._build_decode(batch))(
            batch)

    def _build_decode(self, batch):
        # tpu-lint: cache-key-ok (per-engine cache; no persistent tier)
        model = self.model
        temp = self.temperature
        return lambda b: (model, temp, b)
