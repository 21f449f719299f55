"""window_compiles.served: functions jax traced inside the window, from
the program's ``jax.compile`` spans with ``stage`` ``trace``; 0 in a
steady window. None when the run has no ``serve.sweep`` span: a program
without its batch spans has no compile listener either, and a window
without a batch has nothing to count."""


def read(run):
    spans = run.get("spans", ())
    if not any(name == "serve.sweep" for name, _, _ in spans):
        return None
    return sum(1 for name, _, attrs in spans
               if name == "jax.compile" and attrs.get("stage") == "trace")
