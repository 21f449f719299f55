"""compile_s: host seconds the set-up spent in the calls that compile
the cell's programs or load them from the persistent cache."""


def read(run):
    return run.get("compile_s")
