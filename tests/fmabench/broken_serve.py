"""The engine child with the timed path broken underneath: every token is
altered where it is produced (``InferenceEngine._emit``), and nothing else
changes. ``test_fmabench_correct.py`` runs a whole rehearsal through it and
has to see ``correct`` come out false."""

import sys

from fmabench import serve
from llm_d_fast_model_actuation_tpu.engine import engine as engine_mod

_emit = engine_mod.InferenceEngine._emit


def _emit_wrong(self, req, token, *rest, **kw):
    wrong = 1 + (int(token) + 1) % (self.cfg.model.vocab_size - 1)
    return _emit(self, req, wrong, *rest, **kw)


if __name__ == "__main__":
    engine_mod.InferenceEngine._emit = _emit_wrong
    serve.main(sys.argv[1:])
