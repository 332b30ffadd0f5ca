"""Serving loop: decode steps launched while the step before's tokens had
not been read on the host (`ahead` = 1 on `serving.decode.dispatch`) over
all decode steps dispatched in the window, %. A step that is not ahead was
launched with nothing in flight: the device had run dry first. A program
whose dispatch spans carry no `ahead` launches none ahead and says nothing
here."""


def read(ctx):
    marks = [s["attrs"]["ahead"] for s in ctx.named("serving.decode.dispatch")
             if "ahead" in s.get("attrs", {})]
    if not marks:
        return None
    return 100.0 * sum(1 for a in marks if a) / len(marks)
