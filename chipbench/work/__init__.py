"""Operations and bytes of the work a kernel or a step must do, from the
shapes and the configuration's bit widths alone, never from how the
program implements it: a later change to an implementation cannot move
these counts, so a share of a roofline computed from them cannot pass
100 % unless the time leaves out part of the work."""
