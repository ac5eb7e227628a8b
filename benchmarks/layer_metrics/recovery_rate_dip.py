"""What recovery costs the survivors: the share by which the acknowledged
requests a second fall while ``gp.rec.boot`` is open inside the window (the
boot's seconds, the program's sum, back from the instant ``restart``
returned; cut at the window's last send, after which the client offers
nothing) below the rate between the takeover's last install and the
restart: four live, nobody recovering (all the driver's clock and the
generator's record).  Negative where the loop ran faster meanwhile.  Nothing
where the node never came back, the takeover never ended before the restart,
or the boot opened after the window."""
import numpy as np


def read(run: dict):
    win = run["window"]
    lo, hi = win.get("t_all_installed"), win.get("t_restart")
    done, boot_s = win.get("t_restart_done"), win.get("boot_s")
    if lo is None or hi is None or done is None or not boot_s or hi <= lo:
        return None
    t = win["t_recv"][win["status"] == 0]

    def rate(a, b):
        return float(np.sum((t >= a) & (t < b))) / (b - a)
    b0, b1 = max(done - boot_s, hi), min(done, win["t_deadline"])
    before = rate(lo, hi)
    if b1 <= b0 or not before:
        return None
    return 100.0 * (1.0 - rate(b0, b1) / before)
