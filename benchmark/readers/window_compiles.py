"""Programs that missed the in-memory executable cache inside the window
(built by the backend or loaded from the persistent cache: either is a
stall), per completed operation. Read from the program's compile counter
(`utils.compilation_cache.compile_count`) around the window."""


def read(run, params: dict):
    if not run.completed:
        return None
    return run.window_compiles / len(run.completed)
