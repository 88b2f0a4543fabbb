"""fig5_grid_wireless in the program: the paper grid of `paper_grid` under
node-exclusive interference, registered once per process (the registry
refuses a name it already holds)."""

NAME = "fig5_grid_wireless"


def scenario() -> str:
    from repro.core.graph import paper_grid_problem
    from repro.fleet import Scenario, list_scenarios, register_scenario

    if NAME not in list_scenarios():
        register_scenario(Scenario(
            NAME, lambda seed: paper_grid_problem(), wireless=True,
            description="The paper's Fig. 5(a) grid under node-exclusive "
                        "interference (greedy maximal matching)."))
    return NAME
