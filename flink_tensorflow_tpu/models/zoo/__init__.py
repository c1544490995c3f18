"""Model zoo — jax/flax-native definitions of the reference workloads' models.

The reference ships no model code: it loads frozen TF graphs (Inception-v3
from the TF model zoo, etc.) into embedded sessions.  A TPU-native rebuild
cannot execute those CUDA-era GraphDefs; per SURVEY.md §7 hard part 1, the
idiomatic equivalent is native jax/flax definitions of the same
architectures with weight import from checkpoints — capability parity is
behavioral, not mechanism parity.  One module per BASELINE.json workload:

- :mod:`lenet`     — MNIST LeNet (BASELINE.json:8)
- :mod:`inception` — Inception-v3 (BASELINE.json:7, the north-star model)
- :mod:`resnet`    — ResNet-50 (BASELINE.json:11, DP training)
- :mod:`bilstm`    — BiLSTM text classifier (BASELINE.json:9)
- :mod:`widedeep`  — Wide&Deep recommender (BASELINE.json:10)

Beyond the reference's workloads: :mod:`chartransformer` (the serving
plane's char-level decoder), :mod:`falcon_h1` (a hybrid Mamba-2 +
grouped-query attention language model), :mod:`lfm2_moe` (gated short
convolutions, grouped-query attention and routed experts), :mod:`kimi_k2`
(latent attention, a chip's share of the routed experts beside a shared one)
:mod:`afmoe` (sliding-window and full attention mixed, a gated attention
output, sandwich norms, a share of the routed experts beside a shared one)
and :mod:`mellum` (sliding-window and yarn-scaled full attention mixed, a
softmax router over narrow experts, every one held), each scored a record at
a time on the stream path.
"""

from flink_tensorflow_tpu.models.zoo.registry import ModelDef, get_model_def, register_model_def

__all__ = ["ModelDef", "get_model_def", "register_model_def"]
