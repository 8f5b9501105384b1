"""``prefix_reuse_share`` in a cell whose time to first token is too unsteady
to bound: the same reading, named apart because there it moves
``carbon_mg_per_req`` (a restored prefix is prefill the chip is not charged
for)."""
from bench.metrics.prefix_reuse_share import read  # noqa: F401
