"""Share of the window's ``ooc.entry.copy_c`` spans, in percent, in which
``HostOocRuntime`` wrote the result into the last result it returned: those
that hold an ``ooc.entry.reuse_result`` span, out of those that hold it or
``ooc.entry.alloc_result``.  A program that writes neither leaves nothing
to read."""


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    named = {"ooc.entry.copy_c": [], "ooc.entry.reuse_result": [],
             "ooc.entry.alloc_result": []}
    for e in run.trace.host:
        name = e.name.split("#", 1)[0]
        if name in named:
            named[name].append(e)

    def held(copy, name):
        return any(copy.start <= e.start and e.end <= copy.end
                   for e in named[name])

    copies = [c for c in named["ooc.entry.copy_c"]
              if c.start < hi and lo < c.end]
    reused = sum(held(c, "ooc.entry.reuse_result") for c in copies)
    told = reused + sum(held(c, "ooc.entry.alloc_result") for c in copies)
    return 100.0 * reused / told if told else None
