"""``children/serve.py`` with the lane's chunk size at 16 rows, so that the
toy's prompts of 20 to 60 tokens are prefilled in chunks as the cell's of
thousands are, and, for the tests that hold ``correct`` to be false, a piece
of the model left out of the timed path: ``LANE_FAULT=shared_expert``
computes the expert layers without their shared expert;
``LANE_FAULT=yarn_mscale`` leaves YaRN's ``mscale ** 2`` off the softmax
scale.  With no fault it is the program as it is, chunk size apart."""

import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from kubernetes_deep_learning_tpu.models import kimi_k2  # noqa: E402
from kubernetes_deep_learning_tpu.models import latent_attention  # noqa: E402
from kubernetes_deep_learning_tpu.runtime import decode  # noqa: E402
from perfbench.children import serve  # noqa: E402

decode.PREFILL_CHUNK = 16
FAULT = os.environ.get("LANE_FAULT", "")

if FAULT == "shared_expert":
    moe = kimi_k2.moe

    def moe_without_shared(cfg, layer, u, live, grouped=None):
        silent = {k: v * 0 for k, v in layer["shared"].items()}
        return moe(cfg, dict(layer, shared=silent), u, live, grouped)

    kimi_k2.moe = moe_without_shared
elif FAULT == "yarn_mscale":
    latent_attention.LatentSpec.score_scale = property(
        lambda self: 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim))

if __name__ == "__main__":
    sys.exit(serve.main(sys.argv[1:]))
