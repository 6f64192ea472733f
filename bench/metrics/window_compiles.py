"""Image / Container: serve steps the Container looked up in its
CompileCache during the window, plus JAX traces and backend compiles
(count; 0 when every shape was warmed up)."""


def read(run):
    return float(run.delta["serve_compiles"] + run.window.compiles)
