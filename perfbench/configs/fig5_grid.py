"""fig5_grid in the program: its registry's `paper_grid` scenario."""


def scenario() -> str:
    return "paper_grid"
