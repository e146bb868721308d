"""setup_s (host clock): from the start of the process to the start of
the window — imports, device start-up, trace generation and upload,
building the program's inputs, compiling or loading every program, and
the warm-up calls."""


def read(ctx):
    return ctx["setup_s"]
