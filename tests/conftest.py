import pytest

@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Print the chaos seed (with a one-line repro command) on any failing
    seed-parametrized test, so a CI failure is reproducible verbatim."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed:
        seed = getattr(item, "funcargs", {}).get("seed")
        if seed is not None:
            rep.sections.append((
                "chaos seed",
                f"failing seed: {seed}\nrepro: PYTHONPATH=src python -m "
                f"repro.core.chaos --seed {seed} --check"))
