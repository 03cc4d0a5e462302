"""setup_s (s, host clock): process start to the window's first frame:
JAX and TPU start-up, compile_graph, the frame pool, the warm-up."""


def read(run):
    return run.setup_s
