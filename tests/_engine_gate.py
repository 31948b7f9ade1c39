"""A gate for tests of the serving engine's schedule: stop the worker thread
at the door of one of its own methods while the test acts from outside."""

import threading


def hold(eng, name, nth=1):
    """Stop ``eng``'s worker at the door of its ``nth`` call of ``eng.<name>``:
    ``reached`` is set when it stands there, it goes on once ``release`` is.
    Held at its first ``_land_chunk``, the engine has chunk 2 launched and
    chunk 1 not yet fetched."""
    inner, reached, release, calls = getattr(eng, name), threading.Event(), threading.Event(), [0]

    def held(*a, **k):
        calls[0] += 1
        if calls[0] == nth:
            reached.set()
            assert release.wait(timeout=60)
        return inner(*a, **k)

    setattr(eng, name, held)
    return reached, release
