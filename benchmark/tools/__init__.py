"""Scripts the builder of a benchmark PR runs by hand; no run uses them."""
