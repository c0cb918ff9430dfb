"""Process start to the window's opening (host clock): loading, planning,
weights, compiling or loading compiled programs, and warming up."""


def read(run):
    return run.setup_s
