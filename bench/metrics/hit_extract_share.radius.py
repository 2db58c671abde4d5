"""hit_extract_share.radius: device time of the radius tile loop's hit
extraction over the loop's whole device time, in percent: the union of the
device ops under the scope `allpairs.append_hits` over the union of those
under `allpairs.threshold_scan` (each op's `tf_op` scope path)."""

import spans


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    loop = w.device_seconds("allpairs.threshold_scan")
    if loop <= 0:
        return None
    return 100.0 * w.device_seconds("allpairs.append_hits") / loop
