"""The system under test, as the benchmark builds it from a configuration
file, through the configuration's family (``bench/families``)."""

from __future__ import annotations


def model(cfg: dict):
    """The program's model, checked to take the benchmark's parameter tree."""
    from repro.models import TransformerLM

    from bench import families
    from bench.weights import flatten

    fam = families.load(cfg)
    m = TransformerLM(fam.arch_config(cfg))
    theirs = {k: tuple(v.shape) for k, v in flatten(m.param_shapes()).items()}
    ours = {k: tuple(v) for k, v in fam.shapes(cfg).items()}
    if theirs != ours:
        raise ValueError(f"parameter layout differs: program {theirs} vs "
                         f"benchmark {ours}")
    return m


def program_seed(seed: int) -> int:
    """The 31-bit seed the program's own PRNG state takes."""
    return int(seed) % (2 ** 31 - 1)
