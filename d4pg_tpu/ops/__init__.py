"""Device ops the models call, each chosen from the platform it runs on.

- ``augment``: the DrQ random shift as two batched one-hot matrix products.
- ``attention``: softmax attention for the torso, blockwise ``jax.numpy`` or
  the splash kernel on a TPU.
- ``sparse_attention``: attention over keys an indexer selects at run time
  (an exact top-k threshold; a forward kernel of the repo's own under
  jax's splash backward in its dynamic-mask form, or a blockwise
  ``jax.numpy`` form; the indexer's alignment loss).
- ``grouped``: the experts' grouped matrix products, ``ragged_dot`` or
  megablox ``gmm`` on a TPU.
- ``short_conv``: LFM2's gated depthwise causal convolution of a few taps,
  plain ``jax.numpy`` that XLA fuses on every platform.
"""
