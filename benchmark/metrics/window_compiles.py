"""XLA compilations inside the measured window, from the program's
process-wide compile counter (execution/shapes.compile_count). Every
program is warmed in set-up, so the healthy reading is 0."""


def read(w):
    return float(w.compiles)
