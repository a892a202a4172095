"""Tokens trained per second through master, worker, reader, fused task
step and checkpoint: whole tasks only (see ``_common.tokens_per_second``)."""
from benchmark.metrics._common import tokens_per_second as read  # noqa: F401
