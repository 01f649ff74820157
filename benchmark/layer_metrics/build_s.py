"""Program build: the harness's spans around building the program or engine
and around each first call, less the backend-compile seconds JAX reported
inside them: IR passes, tracing, lowering and cache reads."""


def read(record, trace, cell):
    h = record["harness"]
    return h["build_span_s"] - h["build_compile_s"]
