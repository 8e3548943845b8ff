"""Device ops the models call, each chosen from the platform it runs on.

- ``augment``: the DrQ random shift as two batched one-hot matrix products.
- ``attention``: softmax attention for the torso, blockwise ``jax.numpy`` or
  the splash kernel on a TPU.
- ``grouped``: the experts' grouped matrix products, ``ragged_dot`` or
  megablox ``gmm`` on a TPU.
"""
