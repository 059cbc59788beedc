"""``children/serve.py`` with the program's timed path broken where an
answer is produced, for the tests that hold ``correct`` to be false:
``LANE_FAULT=top_logits`` scales the served top logits of every fifth
decode step by 1.5; ``LANE_FAULT=cache`` zeroes, once a prefill has run,
what the prompt's second position left in the cache of every sublayer
(prefill right, every decode step after it wrong).  With no fault it is the
program as it is."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from kubernetes_deep_learning_tpu.runtime import decode  # noqa: E402
from perfbench.children import serve  # noqa: E402

FAULT = os.environ.get("LANE_FAULT", "")
steps = [0]

if FAULT == "top_logits":
    materialize = decode.DecodeEngine.materialize

    def broken_materialize(self, handle):
        out = materialize(self, handle)
        if len(out.tokens) > 1:
            steps[0] += 1
            if steps[0] % 5 == 4:
                out.top_logits = out.top_logits * 1.5
        return out

    decode.DecodeEngine.materialize = broken_materialize
elif FAULT == "cache":
    prefill = decode.DecodeEngine.prefill

    def broken_prefill(self, slot, prompt_tokens):
        handle = prefill(self, slot, prompt_tokens)
        page = int(self.page_table[slot][1 // self.page_size])
        self._cache = self._cache.at[:, page, 1 % self.page_size].set(0.0)
        return handle

    decode.DecodeEngine.prefill = broken_prefill

if __name__ == "__main__":
    sys.exit(serve.main(sys.argv[1:]))
