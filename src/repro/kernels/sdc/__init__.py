"""SDC (Symmetric Distance Calculation) kernels — the single scoring
substrate for every BEBR index type (FlatSDC, IVF, the distributed
engine, HNSW-lite's numpy walker all score through the same shared
affine epilogue in ``repro.core.binarize_lib.sdc_affine_epilogue``).

Modules:
  * ``sdc``    — fused Pallas scan (+top-k) kernels over flat corpora.
  * ``gather`` — gather-then-scan Pallas kernel for the IVF fine layer
                 (scalar-prefetched probe table; probed lists stream
                 through VMEM with a running top-k).
  * ``ops``    — jit'd public wrappers: padding, top-k search, and the
                 backend-selection flag.
  * ``ref``    — pure-jnp oracles (exact / affine-identity / paper LUT).

Backend-selection flag (``backend=`` on ops, index types, and the
engine):
  * ``"pallas"``    — compiled Pallas kernel; the production TPU path.
  * ``"interpret"`` — same kernels under the Pallas interpreter; used by
                      CPU tests to exercise the real kernel logic.
  * ``"xla"``       — pure-jnp fallback (CPU meshes, debugging); scores
                      are bit-identical because it shares the epilogue.
  * ``"auto"``      — "pallas" when ``jax.default_backend() == "tpu"``,
                      else "xla".

int4 packed code layout (``packed=True``, requires ``n_levels <= 4``):
  document codes are stored nibble-packed at 2 dims/byte — byte ``j``
  holds dim ``2j`` in its low nibble and dim ``2j+1`` in its high nibble
  (``binarize_lib.pack_codes_nibbles``). Two layouts hold those bytes:
    * the flat scan's corpus (``sdc``: ``FlatSDC``, the engine's leaves,
      the bi-granular coarse tier) is uint8 [D//2, N], documents along
      lanes (``ops.pack_scan_corpus``). A tile is unpacked with
      shift+mask on the VPU, its two nibble planes stacked along
      sublanes, and scored by one NN int8 MXU matmul against queries
      permuted even dims then odd;
    * lists that the gather kernel reads (``gather``: IVF, HNSW, the
      rerank's candidates) stay uint8 [L, D//2], a document a row, and
      are scored by two half-width NT matmuls (q_even . lo + q_odd . hi).
  Either way HBM traffic per scanned document halves while integer
  partial sums — and therefore scores — stay bit-identical to the int8
  path. Queries stay unpacked (they are tiny and replicated).
"""
