"""A model as the serving engine sees it that tells a list the logits every
compiled step computed (a host callback: read it after
``jax.effects_barrier()``). For engines whose weights are frozen into the
executables (the CPU's default), where the model need not be a Layer."""
import jax
import numpy as np


class LogitSpy:
    def __init__(self, model, seen):
        self.model, self.cfg, self.seen = model, model.cfg, seen
        self.cache_spec = model.cache_spec

    def eval(self):
        self.model.eval()

    def __call__(self, ids, position_ids=None, attn_mask=None, cache=None):
        logits, cache = self.model(ids, position_ids=position_ids,
                                   attn_mask=attn_mask, cache=cache)
        jax.debug.callback(lambda lg: self.seen.append(np.asarray(lg)),
                           logits._value)
        return logits, cache
