"""Prompt tokens the prefix cache supplied, as a share of those sent."""

from metrics_lib import reuse_share


def read(ctx):
    return 100.0 * reuse_share(ctx)
