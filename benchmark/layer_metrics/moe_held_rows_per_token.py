"""Rows routed to the experts held over tokens, mean over the expert
layers and over the window's reports: the program's counter, reported
with every training loss (0.625 for a balanced sixteenth of a top-10
router; 10 where all experts are held). It says how much work the held
experts did, which `lm_train_mfu` and `moe_experts_roofline` count by.
`None` where the program reports no such counter."""
LAYER = "model step"
UNIT = "rows/token"
SOURCE = "program_counter"


def read(run):
    return run.records.get("counters", {}).get("moe_held_rows_per_token")
