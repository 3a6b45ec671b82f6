"""Page-ins over page-ins plus hot hits in the window, from the state
store's StoreStats (program_counter)."""


def read(ctx):
    return ctx.get("page_in_share")
