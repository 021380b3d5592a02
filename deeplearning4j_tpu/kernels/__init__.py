"""Custom TPU kernels (Pallas) with XLA fallbacks.

Role of the reference's hand-written CUDA kernels (SURVEY N3/N4/N9): most of
libnd4j's kernel library collapses into XLA lowerings, but two genuinely
custom kernels remain worth owning: flash attention (forward and backward
Pallas kernels that keep the (T, T) scores in VMEM; the training path from
``models.transformer.FLASH_MIN_SEQ`` up, read by slabs of lanes straight from
the model's (B, T, H, hd) projections) and the Strom-2015 threshold gradient
codec (the distributed-training compressor, kept for the DCN cross-slice
path). Serving added a third, the routed experts' grouped feed-forward
(``kernels/grouped_ffn.py``, imported from its module by the trace that takes
it, not from here: both of an expert's products and the activation between
them in one kernel that streams each touched expert's weights once, for the
one row tile a decode step's rows are), and a fourth, the paged latent
attention of a decode step (``kernels/paged_latent_attention.py``, imported
the same way: one query a slot against the slot's live pages of latent rows,
fetched from the pool where they lie by the kernel's own asynchronous copies,
eight pages a visit, with an online softmax; no gathered window; the same
walk with two products a key/value head serves grouped-query rows ``[k heads
| v heads]``, ``paged_grouped_attention``: a full layer's pages and, taken
as a slot's own pages in order, a window layer's ring).
"""
from deeplearning4j_tpu.kernels.flash_attention import (flash_attention,
                                                        flash_attention_bthd)
from deeplearning4j_tpu.kernels.threshold import (threshold_decode,
                                                  threshold_encode)

__all__ = ["flash_attention", "flash_attention_bthd", "threshold_encode",
           "threshold_decode"]
