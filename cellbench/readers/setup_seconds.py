"""Set-up time: process start to the start of the window (host clock)."""


def read(cell):
    return cell.setup_s
