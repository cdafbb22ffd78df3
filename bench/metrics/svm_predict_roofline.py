"""Share of its roofline that the fused predict kernel reached: the least
time of every slot the window's waves launched (``work.svm_predict`` at
the wave's row bucket and the bank's SV table) over the device time of the
kernel's events."""
import work


def read(ctx):
    t = ctx.reduced["kernel_s"].get("svm_predict", 0.0) if ctx.reduced else 0.0
    s = ctx.window.get("serve")
    if t <= 0 or not s:
        return None
    least = sum(w["n_slots"] * work.least_s(
        *work.svm_predict(w["m_pad"], s["k"], s["d"], s["p"]), ctx.peaks)
        for w in s["waves"])
    return 100.0 * least / t
