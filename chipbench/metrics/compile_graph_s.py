"""compile_graph_s (s, host clock): compile_graph(backend="pallas") in
set-up (core/compiler.py), from the persistent compile cache after a
checkout's first run."""


def read(run):
    return run.compile_graph_s
