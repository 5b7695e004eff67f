"""TPU kernels and numeric ops: attention, paged KV attention, sampling,
top-k retrieval, quantization. XLA implementations are the portable baseline;
Pallas kernels provide the TPU fast paths (same signatures, tested against
each other)."""

