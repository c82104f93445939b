"""100 * (sum of one attribute) / (sum of other attributes or of durations),
over the program's spans of one name that carry the numerator.

args: span (name), numerator (an attribute's name), denominator (a list of
attributes' names, or of "dur_ms" for the span's duration; an attribute a
span lacks counts 0 there).
"""


def read(ctx, span, numerator, denominator):
    num = den = 0.0
    for s in ctx["spans"]:
        attrs = s.get("attrs", {})
        if s["name"] != span or attrs.get(numerator) is None:
            continue
        num += float(attrs[numerator])
        den += sum(s["dur"] * 1e3 if k == "dur_ms"
                   else float(attrs.get(k) or 0.0) for k in denominator)
    if den <= 0:
        return None
    return 100.0 * num / den
