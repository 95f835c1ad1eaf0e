"""Share of the window's ``ooc.mesh.gather`` spans, in percent, in which
``MeshOocRuntime`` gathered the result into the last result it returned:
those that hold an ``ooc.mesh.reuse_result`` span, out of those that hold
it or ``ooc.mesh.alloc_result``.  A program that writes neither leaves
nothing to read."""


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    named = {"ooc.mesh.gather": [], "ooc.mesh.reuse_result": [],
             "ooc.mesh.alloc_result": []}
    for e in run.trace.host:
        name = e.name.split("#", 1)[0]
        if name in named:
            named[name].append(e)

    def held(gather, name):
        return any(gather.start <= e.start and e.end <= gather.end
                   for e in named[name])

    gathers = [g for g in named["ooc.mesh.gather"]
               if g.start < hi and lo < g.end]
    reused = sum(held(g, "ooc.mesh.reuse_result") for g in gathers)
    told = reused + sum(held(g, "ooc.mesh.alloc_result") for g in gathers)
    return 100.0 * reused / told if told else None
