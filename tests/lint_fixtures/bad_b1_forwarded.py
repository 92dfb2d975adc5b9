"""Known-bad B1: the builder reaches the cache through ONE launch path.

serving/engine.py's shape since ISSUE 33: every family's `_run_*` hands
its builder to one method, which makes the key and the look-up. The key
there carries the family and its bucket dims only, so the sampling axis
the builder bakes in does not ride it.
"""


class MiniEngine:
    def __init__(self, model, temperature):
        self.model = model
        self.temperature = temperature
        self.programs = {}

    def _get_program(self, key, build):
        if key not in self.programs:
            self.programs[key] = build()
        return self.programs[key]

    def _launcher(self, family, dims, builder):
        return self._get_program((family,) + dims, builder)

    def decode(self, batch):
        return self._launcher("decode", (batch,),
                              lambda: self._build_decode(batch))(batch)

    def _build_decode(self, batch):
        # tpu-lint: cache-key-ok (per-engine cache; no persistent tier)
        model = self.model
        temp = self.temperature         # bad: sampling axis not keyed
        return lambda b: (model, temp, b)
