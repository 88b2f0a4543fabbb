"""fattree_k8 in the program: its registry's `fattree_k8` scenario (the
k=8 fabric of `repro.fleet.scenarios.kary_fat_tree`).  A program without
it fails at once with the registry's KeyError."""


def scenario() -> str:
    return "fattree_k8"
