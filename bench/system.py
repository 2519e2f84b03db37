"""How the benchmark builds the system under test from a configuration
file and a seed: the one place that knows the program's config type and
how a seed becomes a key."""
from __future__ import annotations

import jax

from repro.gan.ctgan import CTGANConfig
from repro.tabular.encoders import ColumnSpec


def model_config(m: dict) -> CTGANConfig:
    return CTGANConfig(z_dim=m["z_dim"], gen_hidden=tuple(m["gen_hidden"]),
                       disc_hidden=tuple(m["disc_hidden"]), pac=m["pac"],
                       tau=m["tau"], gp_lambda=m["gp_lambda"],
                       dropout=m["dropout"], lr=m["lr"], b1=m["b1"],
                       b2=m["b2"], batch_size=m["batch_size"])


def schema(m: dict, kinds: list[str]) -> list[ColumnSpec]:
    """The program's column specs for a table of the configuration."""
    n = {"categorical": 0, "continuous": 0}
    out = []
    for k in kinds:
        tag = "cat" if k == "categorical" else "cont"
        out.append(ColumnSpec(f"{m['table']}_{tag}{n[k]}", k,
                              max_modes=m["max_modes"]))
        n[k] += 1
    return out


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class no_annotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` in untraced runs."""

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def annotation(traced: bool):
    return jax.profiler.TraceAnnotation if traced else no_annotation
