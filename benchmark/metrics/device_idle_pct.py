"""Share of the traced steady slice in which no operation ran on the device:
1 - (union of the leaf device-op intervals) / (first whole run's start to last
whole run's end), averaged over the chips used."""


def read(context):
    traced = context["traced"]
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
