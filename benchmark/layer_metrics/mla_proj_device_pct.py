"""Device op time under the scopes of latent attention's projections
(``modules/mla.py``: ``mla_q``, both query products and the norm between
them; ``mla_latent``, the key/value latent's down-projection, norm and
expansion; ``out_proj``) over device op time, in %.  ``out_proj`` is
counted only where a ``self_attn`` module of the program runs ``mla_q``, so
another decoder's ``out_proj`` is not read as latent attention's; 0 where
the program named its operations and none ran under ``mla_q``."""

from benchmark import scope_work


def read(run):
    queries = scope_work.device_pct(run, lambda parts, row: "mla_q" in parts)
    if not queries:
        return queries  # nothing to read (None), or no such layer (0)
    return scope_work.device_pct(run, lambda parts, row: (
        "mla_q" in parts or "mla_latent" in parts
        or ("out_proj" in parts and "self_attn" in parts)))
