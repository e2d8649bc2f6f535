"""Programs lowered inside the window (new to this process: compiled or
read from the persistent cache). Every shape is warmed in set-up, so it
reads 0."""


def read(run):
    return run.window.compiles_in_window
